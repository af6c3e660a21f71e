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


def test_a_gap_is_named_by_the_programs_annotation_not_the_frame(reduced):
    """Each gap of the recorded trace falls into the ``time.sleep`` of
    the host span that launched the program before it: the label is the
    ``TraceAnnotation``, not the interpreter frame ``$time sleep`` that
    covers the same instant and is shorter."""
    labels = sorted(label.split(" | host: ")[1] for label, _ in
                    reduced["gaps"])
    assert labels == ["fixture_host_span_0", "fixture_host_span_1",
                      "fixture_host_span_2"]


# A made-up host: an iteration of the serving loop, its harvest phase,
# the interpreter frames under it and the runtime's own scope innermost.
HOST = [(0.0, 10.0, "sched.iter"), (4.0, 9.0, "sched.harvest"),
        (4.5, 8.0, "$continuous.py:1290 _harvest"),
        (5.0, 7.0, "$array.py:631 _value"),
        (5.5, 6.5, "np.asarray(jax.Array)"),
        (12.0, 13.0, "$threading.py:323 wait"),
        (12.2, 12.8, "$<unknown> acquire"), (20.0, 21.0, "sched.admit")]


@pytest.mark.parametrize("gap,label", [
    # Annotations outermost first, the frames between them left out.
    ((5.9, 6.1), "sched.iter > sched.harvest > np.asarray(jax.Array)"),
    ((7.4, 7.6), "sched.iter > sched.harvest"),
    ((1.0, 1.2), "sched.iter"),
    # No annotation covers it: the innermost frame, as before.
    ((12.4, 12.6), "$<unknown> acquire"),
    # Nothing covers its midpoint: the span that overlaps it most.
    ((19.0, 20.2), "sched.admit"),
    ((30.0, 31.0), "no host span"),
])
def test_host_label(gap, label):
    assert tr._host_label(sorted(HOST), gap) == label


def _reduced_with(events, recorded):
    return {"module_events": events, "recorded": recorded}


def test_whole_events_leaves_out_what_the_traces_edges_cut():
    events = [(0.0, 0.2, "jit_chunk"),        # begins with the trace: cut
              (0.2, 0.21, "jit_pre"),
              (0.21, 0.585, "jit_chunk"), (0.6, 0.976, "jit_chunk"),
              (0.976, 1.07, "jit_chunk")]     # the trace stopped in it
    whole = tr.whole_events(_reduced_with(events, (0.0, 1.07)), "chunk")
    assert whole == pytest.approx([0.375, 0.376])
    # The same launches in a trace that went on: the last is whole now.
    longer = events[:-1] + [(0.976, 1.352, "jit_chunk"),
                            (1.352, 1.36, "jit_pre")]
    assert tr.whole_events(_reduced_with(longer, (0.0, 1.36)), "chunk") == \
        pytest.approx([0.375, 0.376, 0.376])
    assert tr.whole_events(_reduced_with([], None), "chunk") == []


def test_whole_events_on_the_recorded_trace(reduced):
    """Four launches: the first begins with the first recorded instant
    and the last ends with the last one, so neither is known to be whole;
    the two between them are."""
    first, last = reduced["recorded"]
    assert last - first == pytest.approx(reduced["span_s"])
    assert tr.whole_events(reduced, "fixture_step") == \
        pytest.approx([6.523e-6, 6.565e-6])
    assert tr.whole_events(reduced, "no_such_program") == []


def test_top_ops_sums_a_kernels_calls_under_its_name():
    call = ('%{0} = bf16[2,32,4096,128]{{3,2,1,0}} custom-call(%q), '
            'custom_call_target="tpu_custom_call"')
    ops = {"flash_fwd.37": 0.02, "flash_fwd.42": 0.02, "flash_bwd_dq.5": 0.01,
           "fusion.3931": 0.03, "fusion.377": 0.025, "while.95": 1.0}
    text = {n: call.format(n) if n.startswith("flash") else
            f"%{n} = bf16[8]{{0}} fusion(%x)" for n in ops}
    assert tr.top_ops({"ops": ops, "op_text": text}, 3) == [
        ["flash_fwd", pytest.approx(0.04)], ["fusion.3931", 0.03],
        ["fusion.377", 0.025]]


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
