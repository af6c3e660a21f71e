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
