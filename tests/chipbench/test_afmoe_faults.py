"""Planted faults in the AFMoE program that the tiny cell's ``correct``
(``tests/chipbench/tiny_afmoe.py``, the harness's own comparison against
``arch/afmoe.py``'s float32 reference) has to catch: the window mask
dropped, ``route_scale`` or ``route_norm`` dropped, the selection bias
dropped, the shared expert dropped. Each one is a run of the whole cell
on the CPU; they are a file of their own so that they run beside
``test_afmoe.py``'s."""

import pytest

import tiny
import tiny_afmoe
from chipbench import run


@pytest.fixture(scope="module")
def afmoe_root(tmp_path_factory):
    return tiny_afmoe.grow_afmoe(tiny.write_tree(
        str(tmp_path_factory.mktemp("afmoe_faults"))))


@pytest.fixture(autouse=True)
def _no_cache(no_compile_cache):
    pass


def _window_dropped(mp):
    from serverless_learn_tpu.models import transformer

    mp.setattr(transformer.TransformerConfig, "window",
               lambda self, kind: 0)


def _routing(**change):
    def plant(mp):
        from serverless_learn_tpu.ops import moe

        real = moe.route

        def route(logits, top_k, score="softmax", bias=None, norm=True,
                  scale=1.0):
            kw = dict(score=score, bias=bias, norm=norm, scale=scale)
            kw.update(change)
            return real(logits, top_k, **kw)

        mp.setattr(moe, "route", route)
    return plant


def _shared_expert_dropped(mp):
    from serverless_learn_tpu.models import transformer

    real = transformer.MlpBlock

    class NoShared(real):
        def __call__(self, x):
            y = real.__call__(self, x)
            return 0 * y if self.name == "shared" else y

    mp.setattr(transformer, "MlpBlock", NoShared)


FAULTS = {"window_mask_dropped": _window_dropped,
          "route_scale_dropped": _routing(scale=1.0),
          "route_norm_dropped": _routing(norm=False),
          "selection_bias_dropped": _routing(bias=None),
          "shared_expert_dropped": _shared_expert_dropped}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(afmoe_root, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    line = run.run_cell(tiny_afmoe.CELL, seed=2 ** 31 + 41, seconds=0.3,
                        trace=False, root=afmoe_root, require_chip=False)
    assert line["correct"] is False
    n = line["checked"]["served_logit_gap"]
    assert n["value"] > 10 * n["limit"]
    assert line["checked"]["replies_wrong_length"]["value"] == 0
