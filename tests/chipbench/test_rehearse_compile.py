"""Compile-only guard, no chip: the flash-attention kernels of the
training cell at its real widths (2 x 4096 tokens, 32/8 heads of 128)
compile for a described v5e, forward and backward, as real Mosaic kernels.
Kept in ONE file and behind a fixture: only the worker that runs this
file loads the TPU compiler."""

from unittest import mock

import pytest


@pytest.fixture(scope="module")
def one_chip():
    from jax.sharding import SingleDeviceSharding

    from chipbench import rehearse

    try:
        dev, _ = rehearse._one_chip()
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(dev)


def test_flash_kernels_compile_at_the_cells_widths(one_chip):
    import jax
    import jax.numpy as jnp

    from chipbench.cell import load_cell
    from serverless_learn_tpu.ops.attention import dot_product_attention

    cell = load_cell("mistral7b-lora-train-4k")
    sz, t = cell.sizes, cell.traffic
    B, T = t["sequences_per_step"], t["tokens_per_sequence"]
    q = jax.ShapeDtypeStruct((B, T, sz.n_heads, sz.head_dim), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, T, sz.n_kv_heads, sz.head_dim),
                              jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return dot_product_attention(q, k, v, causal=True,
                                     impl="auto").astype(jnp.float32).sum()

    with mock.patch("jax.default_backend", lambda: "tpu"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
    # `auto` took the flash path: forward, dq and dk/dv kernels.
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_the_rehearsal_takes_a_dropped_in_serving_cell(one_chip, tmp_path,
                                                       capsys):
    """``rehearse.py serve <depth> --workload <cell>`` on a cell that a
    later PR added as files (``tiny.grow_moe``: another architecture,
    serving only): its largest decode and prefill programs, and its
    reference's forward pass, compile for the described chip; a cell of
    the wrong kind is refused by name."""
    import tiny
    from chipbench import rehearse

    root = tiny.grow_moe(tiny.write_tree(str(tmp_path)), serving_only=True)
    programs = rehearse.rehearse_serve(2, "tiny-moe-backlog", root)
    assert [p["program"].split(":")[0] for p in programs] == \
        ["tiny-moe-backlog"] * 2
    assert any("decode chunk" in p["program"] for p in programs)
    assert any("prefill chunk" in p["program"] for p in programs)
    assert all(p["needs_GiB"] < 1.0 for p in programs)
    # The engine was built with the cell's own slots, not the default 8.
    assert "nb=4" in programs[0]["program"]
    ref = rehearse.rehearse_reference(2, "tiny-moe-backlog", root)
    assert len(ref) == 1 and "reference forward" in ref[0]["program"]
    with pytest.raises(SystemExit, match="not a train cell"):
        rehearse.rehearse_train(2, "tiny-moe-backlog", root)
