"""Planted faults in the LFM2-MoE program that the tiny cell's ``correct``
(``tests/chipbench/tiny_lfm2.py``, the harness's own comparison against
``arch/lfm2_moe.py``'s float32 reference) has to catch: the conv layers'
carried inputs dropped between prefill chunks, the output gate ``C *``
left out of the conv mixer, the expert bias left out of selection. Each
one is a run of the whole cell on the CPU; they are a file of their own
so that they run beside ``test_lfm2_moe.py``'s."""

import pytest

import tiny
import tiny_lfm2
from chipbench import run


@pytest.fixture(scope="module")
def lfm2_root(tmp_path_factory):
    return tiny_lfm2.grow_lfm2(tiny.write_tree(
        str(tmp_path_factory.mktemp("lfm2_faults"))))


@pytest.fixture(autouse=True)
def _no_cache(no_compile_cache):
    pass


def _carry_dropped(mp):
    """Every prefill row-chunk starts from zero carried inputs: a prompt
    of more than one chunk (20 to 72 tokens in chunks of 32) forgets the
    inputs before its later chunks, in a program and across programs."""
    from serverless_learn_tpu.inference import kvcache

    real = kvcache.take_slots

    def forgetful(tree, names, ids, fresh=None):
        if fresh is not None:
            fresh = fresh | True
        return real(tree, names, ids, fresh)

    mp.setattr(kvcache, "take_slots", forgetful)


def _output_gate_dropped(mp):
    """The conv mixer as it is, but ``out_proj(conv(B * x))``."""
    import flax.linen as nn
    import jax.numpy as jnp

    from serverless_learn_tpu.models import transformer
    from serverless_learn_tpu.ops import ssm

    class Ungated(transformer.ShortConvMixer):
        @nn.compact
        def __call__(self, h, *, decode=False, prefill=False, extend=False,
                     seq_lengths=None, link=None):
            cfg = self.cfg
            d, K = cfg.d_model, cfg.ssm_conv
            b, _, u = jnp.split(transformer._proj(cfg, 3 * d, "in_proj")(h),
                                3, axis=-1)
            taps = self.param("conv_kernel", nn.initializers.zeros, (K, d),
                              cfg.param_dtype)
            carried = (decode or prefill or extend) and self.has_variable(
                "cache", "conv_state")
            if decode or prefill or extend:
                cs = self.variable("cache", "conv_state", jnp.zeros,
                                   (h.shape[0], K - 1, d), cfg.dtype)
            conv_in = cs.value if carried else jnp.zeros(
                (h.shape[0], K - 1, d), h.dtype)
            w, conv_out = ssm.causal_conv(
                b * u, conv_in, taps, None, seq_lengths if carried else None,
                link if carried and extend else None)
            if carried:
                cs.value = conv_out
            return transformer._proj(cfg, d, "out_proj")(w.astype(cfg.dtype))

    mp.setattr(transformer, "ShortConvMixer", Ungated)


def _selection_bias_dropped(mp):
    from serverless_learn_tpu.ops import moe

    real = moe.route

    def route(logits, top_k, score="softmax", bias=None, norm=True,
              scale=1.0):
        return real(logits, top_k, score=score, bias=None, norm=norm,
                    scale=scale)

    mp.setattr(moe, "route", route)


FAULTS = {"carried_inputs_dropped_between_chunks": _carry_dropped,
          "output_gate_dropped": _output_gate_dropped,
          "selection_bias_dropped": _selection_bias_dropped}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(lfm2_root, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    line = run.run_cell(tiny_lfm2.CELL, seed=2 ** 31 + 43, seconds=0.3,
                        trace=False, root=lfm2_root, require_chip=False)
    assert line["correct"] is False
    n = line["checked"]["served_logit_gap"]
    assert n["value"] > 10 * n["limit"]
    assert line["checked"]["replies_wrong_length"]["value"] == 0
