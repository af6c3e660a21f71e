"""``trace_reduce.py`` on a small trace recorded on a TPU v5e (PR 25's
chip run): four launches of one jitted program of three fused matmuls,
each inside a host ``TraceAnnotation``, with a 2 ms sleep between them."""

import pytest

import tiny
from chipbench import trace_reduce as tr


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(tiny.FIXTURE_TRACE)


def test_busy_is_the_union_of_the_ops_lane_only(reduced):
    assert reduced["n_devices"] == 1
    # 4 launches x (3.9 + 2.4) us of fusions: some 25 us busy in a span of
    # 10.5 ms. Counting the "XLA Modules" lane as ops would double it.
    assert reduced["busy_s"] == pytest.approx(25.7e-6, rel=0.02)
    assert reduced["span_s"] == pytest.approx(10.5e-3, rel=0.02)
    assert reduced["busy_s"] == pytest.approx(sum(reduced["ops"].values()))


def test_ops_and_modules_are_named_and_counted(reduced):
    assert set(reduced["modules"]) == {"jit_fixture_step"}
    assert len(reduced["modules"]["jit_fixture_step"]) == 4
    top = tr.top_ops(reduced, 2)
    assert [name for name, _ in top] == ["convolution_tanh_fusion.1",
                                         "convolution_tanh_fusion"]
    assert reduced["op_counts"]["convolution_tanh_fusion"] == 4
    assert "kind=kOutput" in reduced["op_text"]["convolution_tanh_fusion"]


def test_idle_gaps_are_set_against_the_hosts_spans(reduced):
    gaps = reduced["gaps"]
    assert len(gaps) == 3 and all(s > 3e-3 for _, s in gaps)
    assert all(label.startswith("jit_fixture_step -> ") for label, _ in gaps)
    assert any("fixture_host_span" in label for label, _ in gaps)


@pytest.mark.parametrize("intervals,busy,n_gaps", [
    ([], 0.0, 0),
    ([(0.0, 1.0, "a")], 1.0, 0),
    ([(0.0, 1.0, "a"), (0.5, 2.0, "b")], 2.0, 0),          # overlap
    ([(0.0, 1.0, "a"), (0.2, 0.4, "b"), (3.0, 4.0, "c")], 2.0, 1),  # nested
    ([(0.0, 1.0, "a"), (1.0, 2.0, "b")], 2.0, 0),          # touching
])
def test_union_seconds(intervals, busy, n_gaps):
    got, gaps = tr.union_seconds(intervals)
    assert got == pytest.approx(busy) and len(gaps) == n_gaps


def test_a_trace_with_no_tpu_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no '/device:TPU"):
        tr.reduce_trace(tr.find_xplane(str(tmp_path)))


def test_names():
    assert tr.module_name("jit_step_fn(123)") == "jit_step_fn"
    assert tr.op_name("%fusion.3 = bf16[8]{0} fusion(%x)") == "fusion.3"
