"""Tier-1 tests for the round-25 JAX program analysis: the four static
rules (SLT010 dtype flow, SLT011 donation safety, SLT012 recompile
hazards, SLT013 sharding drift), the runtime compile monitor
(analysis/jitcheck.py), the jaxpr harness (analysis/shardcheck.py) and
the `slt jit` replay CLI.

Static-rule tests use the test_analysis fixture idiom (known-bad code
fires, known-good passes); monitor tests use LOCAL JitMonitor instances
via jitcheck.scoped() so they stay deterministic under a session-global
SLT_JITCHECK=1 install; session-failure tests run a seeded pytest
subprocess and assert exit code 5 (lockcheck=3, racecheck=4,
jitcheck=5).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from serverless_learn_tpu.analysis import jitcheck, shardcheck
from serverless_learn_tpu.analysis.engine import discover, run_check
from serverless_learn_tpu.analysis.rules import (slt010_dtype_flow,
                                                 slt011_donation_safety,
                                                 slt012_recompile_hazard,
                                                 slt013_sharding_drift)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(tmp_path, files):
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return str(tmp_path)


def _run_rule(rule, root):
    return rule.run(discover(root))


# -- SLT010: dtype flow ------------------------------------------------------

def test_slt010_bf16_reduction_fires(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def loss(x):
            h = x.astype(jnp.bfloat16)
            return jnp.sum(h)
        """})
    fs = _run_rule(slt010_dtype_flow, root)
    assert any("sum()" in f.message and "bfloat16" in f.message
               for f in fs), fs


def test_slt010_f32_accumulator_escape_hatch_is_clean(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def loss(x):
            h = x.astype(jnp.bfloat16)
            a = jnp.sum(h, dtype=jnp.float32)
            b = jnp.sum(h.astype(jnp.float32))
            return a + b
        """})
    assert _run_rule(slt010_dtype_flow, root) == []


def test_slt010_method_reduction_and_unknown_dtype(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def bad(x):
            h = x.astype(jnp.bfloat16)
            return h.mean()

        @jax.jit
        def unknown_is_quiet(x, d):
            h = x.astype(d)
            return jnp.sum(h)
        """})
    fs = _run_rule(slt010_dtype_flow, root)
    assert len(fs) == 1 and "mean()" in fs[0].message, fs


def test_slt010_f64_in_jit_fires(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return x + jnp.zeros((4,), dtype=jnp.float64)
        """})
    fs = _run_rule(slt010_dtype_flow, root)
    assert any("float64" in f.message for f in fs), fs


def test_slt010_mixed_precision_binop_warns(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            a = x.astype(jnp.bfloat16)
            b = jnp.zeros((4,), jnp.float32)
            return a + b
        """})
    fs = _run_rule(slt010_dtype_flow, root)
    assert any(f.severity == "warning" and "upcast" in f.message
               for f in fs), fs


def test_slt010_param_dtype_contract(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/config.py": """\
        from dataclasses import dataclass

        @dataclass
        class TrainConfig:
            dtype: str = "bfloat16"
            param_dtype: str = "bfloat16"
        """})
    fs = _run_rule(slt010_dtype_flow, root)
    assert any("param_dtype" in f.message and "master" in f.message
               for f in fs), fs


# -- SLT011: donation safety -------------------------------------------------

def test_slt011_read_after_donation_fires(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, batch):
            return state, 1.0

        def train(state, batches):
            for batch in batches:
                new_state, loss = step(state, batch)
                emit(state["params"])
                state = new_state
        """})
    fs = _run_rule(slt011_donation_safety, root)
    assert any("donated to step()" in f.message for f in fs), fs


def test_slt011_rebind_is_clean(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, batch):
            return state, 1.0

        def train(state, batches):
            for batch in batches:
                state, loss = step(state, batch)
                emit(state["params"])
            return state
        """})
    assert _run_rule(slt011_donation_safety, root) == []


def test_slt011_self_attr_and_factory_paths(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax

        def make_step():
            inner = jax.jit(lambda s, b: (s, 1.0), donate_argnums=(0,))
            return inner

        def factory_bug(state, batch):
            fn = make_step()
            out, _ = fn(state, batch)
            return state.params

        class Engine:
            def __init__(self):
                self._step = jax.jit(lambda s, b: (s, 1.0),
                                     donate_argnums=(0,))

            def run(self, batch):
                st, _ = self._step(self._state, batch)
                x = self._state["pages"]
                self._state = st

            def run_ok(self, batch):
                self._state, _ = self._step(self._state, batch)
                return self._state["pages"]
        """})
    fs = _run_rule(slt011_donation_safety, root)
    msgs = "\n".join(f.message for f in fs)
    assert "state.params read in factory_bug" in msgs, fs
    assert "self._state['pages'] read in run " in msgs, fs
    assert "run_ok" not in msgs, fs


def test_slt011_branch_union_and_loop_second_iteration(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, batch):
            return state, 1.0

        def branch_bug(state, batch, flag):
            if flag:
                out, _ = step(state, batch)
            else:
                out = state
            return state

        def loop_bug(state, batch):
            for _ in range(3):
                out, _ = step(state, batch)
            return out
        """})
    fs = _run_rule(slt011_donation_safety, root)
    assert any(f.message.startswith("state read in branch_bug")
               for f in fs), fs
    assert any(f.message.startswith("state read in loop_bug")
               for f in fs), fs


def test_slt011_non_literal_donate_mask_is_quiet(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        from functools import partial

        donate = (0,) if True else ()

        @partial(jax.jit, donate_argnums=donate)
        def step(state, batch):
            return state, 1.0

        def train(state, batch):
            out, _ = step(state, batch)
            return state
        """})
    assert _run_rule(slt011_donation_safety, root) == []


# -- SLT012: recompile hazards -----------------------------------------------

def test_slt012_traced_branch_fires_static_and_none_are_clean(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        from functools import partial

        @jax.jit
        def bad(x, n):
            if n > 4:
                return x * 2
            return x

        @partial(jax.jit, static_argnums=(1,))
        def ok_static(x, n):
            if n > 4:
                return x * 2
            return x

        @jax.jit
        def ok_none(x, mask=None):
            if mask is None:
                return x
            return x * mask
        """})
    fs = _run_rule(slt012_recompile_hazard, root)
    assert len(fs) == 1, fs
    assert "bad branches on traced parameter(s) n" in fs[0].message


def test_slt012_unhashable_static_arg_fires(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        import jax.numpy as jnp
        from functools import partial

        @partial(jax.jit, static_argnums=(1,))
        def make(x, shape):
            return jnp.zeros(shape) + x

        def caller(x):
            return make(x, [4, 4])
        """})
    fs = _run_rule(slt012_recompile_hazard, root)
    assert any("unhashable" in f.message.lower()
               or "hashable" in f.message for f in fs), fs


def test_slt012_jit_in_loop_warns_memoized_is_clean(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax

        def bad(fs, x):
            outs = []
            for f in fs:
                outs.append(jax.jit(f)(x))
            return outs

        def ok(fs, cache):
            for i, f in enumerate(fs):
                cache[i] = jax.jit(f)
            return cache
        """})
    fs = _run_rule(slt012_recompile_hazard, root)
    assert len(fs) == 1 and fs[0].severity == "warning", fs
    assert "loop" in fs[0].message


def test_slt012_raw_len_shape_key_fires_bucketed_is_clean(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        import jax
        from serverless_learn_tpu.analysis import jitcheck

        @jitcheck.bucket
        def _nb(n):
            return max(8, 1 << (n - 1).bit_length())

        class Eng:
            def _shape_jit(self, nb):
                key = (nb,)
                if key not in self._cache:
                    self._cache[key] = jax.jit(lambda s: s)
                return self._cache[key]

            def good(self, rows):
                nb = _nb(len(rows))
                return self._shape_jit(nb)

            def clamped(self, rows, cap):
                nb = min(_nb(len(rows)), cap)
                return self._shape_jit(nb)

            def bad(self, rows):
                nb = len(rows)
                return self._shape_jit(nb)
        """})
    fs = _run_rule(slt012_recompile_hazard, root)
    assert len(fs) == 1, fs
    assert "raw len()" in fs[0].message and "_shape_jit" in fs[0].message


# -- SLT013: sharding drift --------------------------------------------------

_SLT013_BASE = """\
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax.lax import with_sharding_constraint

    AXIS_NAMES = ("dp", "tp")
"""


def test_slt013_undeclared_axis_fires_declared_is_clean(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": _SLT013_BASE + """\

    def good(x):
        return with_sharding_constraint(x, P("dp", None))

    def typo(x):
        return with_sharding_constraint(x, P("ftp", None))

    def tuple_drift(x):
        return with_sharding_constraint(x, P(("dp", "fsdp"),))
    """})
    fs = _run_rule(slt013_sharding_drift, root)
    axes = sorted(f.message.split("'")[1] for f in fs)
    assert axes == ["fsdp", "ftp"], fs


def test_slt013_compose_axis_drift_fires(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": _SLT013_BASE + """\

    def f(spec, shape, mesh):
        return compose_axis(spec, shape, mesh, "zp")
    """})
    fs = _run_rule(slt013_sharding_drift, root)
    assert any("compose_axis" in f.message and "'zp'" in f.message
               for f in fs), fs


def test_slt013_constraint_in_scan_body_fires_outside_is_clean(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": _SLT013_BASE + """\

    def accum(params, batches):
        def body(acc, mb):
            g = jnp.zeros((4,))
            g = with_sharding_constraint(g, P("dp"))
            return acc + g, None
        out, _ = jax.lax.scan(body, jnp.zeros((4,)), batches)
        return with_sharding_constraint(out, P("dp"))
    """})
    fs = _run_rule(slt013_sharding_drift, root)
    assert len(fs) == 1 and "scan body" in fs[0].message, fs


def test_slt013_no_declared_axes_stays_quiet(tmp_path):
    root = _tree(tmp_path, {"serverless_learn_tpu/m.py": """\
        from jax.sharding import PartitionSpec as P

        SPEC = P("whatever")
        """})
    assert _run_rule(slt013_sharding_drift, root) == []


# -- seeded-defect tree: all four rules at once ------------------------------

_SEEDED = {
    "serverless_learn_tpu/dtypes.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def loss(x):
            h = x.astype(jnp.bfloat16)
            return jnp.sum(h)
        """,
    "serverless_learn_tpu/donate.py": """\
        import jax
        from functools import partial

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, batch):
            return state, 1.0

        def train(state, batch):
            out, _ = step(state, batch)
            return state
        """,
    "serverless_learn_tpu/recompile.py": """\
        import jax

        @jax.jit
        def bad(x, n):
            if n > 4:
                return x * 2
            return x
        """,
    "serverless_learn_tpu/shard.py": """\
        from jax.sharding import PartitionSpec as P
        from jax.lax import with_sharding_constraint

        AXIS_NAMES = ("dp", "tp")

        def f(x):
            return with_sharding_constraint(x, P("ftp"))
        """,
}


def test_seeded_defect_tree_fails_all_four_rules(tmp_path):
    root = _tree(tmp_path, _SEEDED)
    rep = run_check(root, baseline_path="baseline.json")
    assert not rep["ok"]
    rules_hit = {f["rule"] for f in rep["findings"]}
    assert {"SLT010", "SLT011", "SLT012", "SLT013"} <= rules_hit, \
        rules_hit


def test_repo_at_head_is_clean_for_new_rules():
    rep = run_check(REPO, rule_ids=["SLT010", "SLT011", "SLT012",
                                    "SLT013"])
    assert rep["ok"], rep["findings"]


# -- jitcheck monitor (local monitors via scoped()) --------------------------

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _instrumented_step():
    """A donating jit created from THIS file (tests/ is in scope)."""
    was = jitcheck.installed()
    jitcheck.install()
    step = jax.jit(lambda s, b: (s + b, s.sum()), donate_argnums=(0,))
    if not was and not jitcheck.enabled_by_env():
        jitcheck.uninstall()  # leave the global patch as we found it
    if not isinstance(step, jitcheck._InstrumentedJit):
        pytest.skip("jax.jit already bound before instrumentation")
    return step


def test_monitor_counts_compiles_within_budget():
    step = _instrumented_step()
    mon = jitcheck.JitMonitor("unit")
    with jitcheck.scoped(mon):
        s, b = jnp.zeros((4,)), jnp.ones((4,))
        s, _ = step(s, b)
        s, _ = step(s, b)   # same shape: cached, no second compile
    assert mon.site_compiles() == {step.site: 1}
    assert mon.violations() == []
    rec = mon.records()[0]
    assert rec["donate"] == [0]
    assert rec["args"][0].startswith("float32[4]")
    assert rec["elapsed_ms"] > 0


def test_monitor_budget_overrun_fails():
    step = _instrumented_step()
    mon = jitcheck.JitMonitor("unit")
    mon.declare_budget(step.site, max_compiles_per_jit=1)
    with jitcheck.scoped(mon):
        s, _ = step(jnp.zeros((4,)), jnp.ones((4,)))
        s, _ = step(jnp.zeros((8,)), jnp.ones((8,)))  # 2nd signature
    kinds = [v["kind"] for v in mon.violations()]
    assert kinds == ["budget"], mon.violations()
    with pytest.raises(jitcheck.JitCheckViolation):
        mon.assert_clean()


def test_monitor_frozen_window_recompile_fails():
    step = _instrumented_step()
    mon = jitcheck.JitMonitor("unit")
    with jitcheck.scoped(mon):
        step(jnp.zeros((4,)), jnp.ones((4,)))       # warm
        with jitcheck.frozen("measured"):
            step(jnp.zeros((4,)), jnp.ones((4,)))   # cached: fine
            step(jnp.zeros((8,)), jnp.ones((8,)))   # compile: violation
    vio = mon.violations()
    assert [v["kind"] for v in vio] == ["frozen"], vio
    assert vio[0]["label"] == "measured"
    assert vio[0]["stack"], "frozen violation must carry the stack"


def test_monitor_detects_donated_buffer_reuse():
    step = _instrumented_step()
    mon = jitcheck.JitMonitor("unit")
    with jitcheck.scoped(mon):
        s, b = jnp.zeros((4,)), jnp.ones((4,))
        s, _ = step(s, b)
        out, _ = step(s, b)   # donates s, NOT rebound
        try:
            step(s, b)        # reuse: logical violation...
        except ValueError:
            pass              # ...and jax itself may also object
    vio = [v for v in mon.violations() if v["kind"] == "donation_reuse"]
    assert len(vio) == 1, mon.violations()
    assert vio[0]["donated"]["site"] == step.site
    assert "rebound" in vio[0]["why"]


def test_monitor_rebind_pattern_is_clean():
    step = _instrumented_step()
    mon = jitcheck.JitMonitor("unit")
    with jitcheck.scoped(mon):
        s, b = jnp.zeros((4,)), jnp.ones((4,))
        for _ in range(4):
            s, _ = step(s, b)  # the sanctioned rebind loop
    assert mon.violations() == []


def test_monitor_jsonl_replay_round_trip(tmp_path):
    step = _instrumented_step()
    log = tmp_path / "jit.jsonl"
    mon = jitcheck.JitMonitor("unit", log_path=str(log))
    mon.declare_budget(step.site, max_compiles_per_jit=1)
    with jitcheck.scoped(mon):
        step(jnp.zeros((4,)), jnp.ones((4,)))
        with jitcheck.frozen("w"):
            step(jnp.zeros((8,)), jnp.ones((8,)))  # frozen AND over budget
    mon.close_log()
    rep = jitcheck.replay_log(str(log))
    kinds = sorted(v["kind"] for v in rep["violations"])
    assert kinds == ["budget", "frozen"], rep["violations"]
    assert rep["sites"][step.site] == 2
    # live monitor and replay agree
    assert sorted(v["kind"] for v in mon.violations()) == kinds


def test_self_check_passes():
    assert jitcheck.self_check() == []


# -- slt jit CLI -------------------------------------------------------------

def test_cli_jit_replay_exit_codes(tmp_path, capsys):
    from serverless_learn_tpu.cli import main

    site = "serverless_learn_tpu/inference/continuous.py:_paged_chunk_jit"
    bad = tmp_path / "bad.jsonl"
    bad.write_text("".join(json.dumps(e) + "\n" for e in [
        {"ev": "declare", "site": site, "budget": 1},
        {"ev": "compile", "site": site, "n": 2, "args": ["f32[8]"],
         "stack": ["a.py:1 in hot"]},
    ]))
    rc = main(["jit", str(bad), "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 2 and out["ok"] is False
    assert out["violations"][0]["kind"] == "budget"

    good = tmp_path / "good.jsonl"
    good.write_text(json.dumps(
        {"ev": "compile", "site": site, "n": 1, "args": ["f32[8]"]})
        + "\n")
    assert main(["jit", str(good)]) == 0
    capsys.readouterr()


def test_cli_jit_self_check(capsys):
    from serverless_learn_tpu.cli import main

    assert main(["jit", "--self-check"]) == 0
    assert "verdict engine OK" in capsys.readouterr().out


# -- session failure end-to-end (exit 5) -------------------------------------

_SUB_CONFTEST = """\
    import os, sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, {repo!r})
    from serverless_learn_tpu.analysis import jitcheck
    jitcheck.install()
    import pytest

    def pytest_sessionfinish(session, exitstatus):
        mon = jitcheck.monitor()
        print()
        print(mon.report())
        if mon.violations():
            pytest.exit("jitcheck violations", returncode=5)
"""


def _run_sub_session(tmp_path, test_body):
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "conftest.py").write_text(
        textwrap.dedent(_SUB_CONFTEST).format(repo=REPO))
    (tests / "test_seeded.py").write_text(textwrap.dedent(test_body))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO, SLT_JITCHECK="1")
    env.pop("SLT_JITCHECK_LOG", None)
    return subprocess.run(
        [sys.executable, "-m", "pytest", str(tests), "-q", "-p",
         "no:cacheprovider"],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=str(tmp_path))


def test_surprise_recompile_fails_the_session(tmp_path):
    """A compile past a declared budget exits 5 with both traces."""
    proc = _run_sub_session(tmp_path, """\
        import jax, jax.numpy as jnp
        from serverless_learn_tpu.analysis import jitcheck

        def test_budget_breach():
            f = jax.jit(lambda x: x * 2)
            jitcheck.monitor().declare_budget(f.site, 1)
            f(jnp.zeros((4,)))
            f(jnp.zeros((8,)))   # second signature on one jit object
        """)
    assert proc.returncode == 5, proc.stdout + proc.stderr
    assert "VIOLATION [budget]" in proc.stdout


def test_donated_reuse_fails_the_session(tmp_path):
    proc = _run_sub_session(tmp_path, """\
        import jax, jax.numpy as jnp
        from serverless_learn_tpu.analysis import jitcheck

        def test_reuse():
            step = jax.jit(lambda s, b: (s + b, s.sum()),
                           donate_argnums=(0,))
            s, b = jnp.zeros((4,)), jnp.ones((4,))
            s, _ = step(s, b)
            out, _ = step(s, b)      # donates s without rebinding
            try:
                step(s, b)           # reuse
            except ValueError:
                pass
        """)
    assert proc.returncode == 5, proc.stdout + proc.stderr
    assert "VIOLATION [donation_reuse]" in proc.stdout


# -- shardcheck harness ------------------------------------------------------

def test_shardcheck_flags_constraint_inside_scan(devices):
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.Mesh(jax.devices(), ("dp",))

    def bad(xs):
        def body(acc, x):
            y = jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(mesh, P("dp")))
            return acc + y, None
        out, _ = jax.lax.scan(body, jnp.zeros((8,)), xs)
        return out

    report = shardcheck.audit(bad, jnp.ones((4, 8)))
    assert report.in_scan, "constraint inside scan body must be seen"
    assert "dp" in report.axes_used
    with pytest.raises(AssertionError, match="PER ITERATION"):
        report.assert_no_loop_constraints()


def test_shardcheck_constraint_outside_scan_is_clean(devices):
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.Mesh(jax.devices(), ("dp",))

    def good(xs):
        def body(acc, x):
            return acc + x, None
        out, _ = jax.lax.scan(body, jnp.zeros((8,)), xs)
        return jax.lax.with_sharding_constraint(
            out, jax.sharding.NamedSharding(mesh, P("dp")))

    report = shardcheck.audit(good, jnp.ones((4, 8)))
    assert report.in_scan == []
    assert report.outside_with_axis("dp")
    report.assert_no_loop_constraints()


# -- acceptance: warmed engine + train loop under the monitor ----------------

def test_warmed_engine_and_train_loop_have_no_unexpected_compiles(devices):
    """The ISSUE 20 acceptance path: a warmed ContinuousBatchingEngine
    decode and a tiny train loop, both under the monitor — every
    compile lands inside a declared budget and the post-warmup frozen
    window sees none."""
    was = jitcheck.installed()
    jitcheck.install()
    try:
        mon = jitcheck.JitMonitor("acceptance")
        with jitcheck.scoped(mon):
            # -- engine: warm one admit bucket, then decode frozen ----
            from serverless_learn_tpu.inference.continuous import (
                ContinuousBatchingEngine)
            from serverless_learn_tpu.models.registry import get_model

            bundle = get_model("llama_tiny", dtype=jnp.float32,
                               param_dtype=jnp.float32, max_seq_len=64)
            params = bundle.module.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 8), jnp.int32))["params"]
            eng = ContinuousBatchingEngine(bundle.module, params,
                                           max_slots=4, chunk_size=4)
            try:
                # first request compiles the admit bucket + chunk step
                eng.submit([5, 9, 11], 4, temperature=0.0, top_k=0,
                           eos_id=None, seed=0)
                warm_sites = dict(mon.site_compiles())
                with jitcheck.frozen("post-warmup decode"):
                    # same buckets: zero new compiles allowed
                    out = eng.submit([7, 3, 2], 4, temperature=0.0,
                                     top_k=0, eos_id=None, seed=0)
                assert "error" not in out
            finally:
                eng.stop()
            assert [v for v in mon.violations()] == [], mon.report()
            assert any("continuous.py" in s for s in warm_sites), \
                warm_sites

            # -- tiny train loop: one compile per jit object ----------
            from serverless_learn_tpu.config import (
                DataConfig, ExperimentConfig, MeshConfig,
                OptimizerConfig, TrainConfig)
            from serverless_learn_tpu.data.datasets import SyntheticSource
            from serverless_learn_tpu.training.train_step import (
                build_trainer)

            cfg = ExperimentConfig(
                model="mlp_mnist", mesh=MeshConfig(dp=8),
                optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
                train=TrainConfig(batch_size=16, num_steps=3),
                data=DataConfig(seq_len=16))
            trainer = build_trainer(cfg)
            state = trainer.init()
            src = SyntheticSource(trainer.bundle.make_batch, cfg.data,
                                  16, seed=7)
            it = iter(src)
            state, _ = trainer.step(state, trainer.shard_batch(next(it)))
            with jitcheck.frozen("steady-state training"):
                for _ in range(2):
                    state, _ = trainer.step(
                        state, trainer.shard_batch(next(it)))
        assert mon.violations() == [], mon.report()
        ts = "serverless_learn_tpu/training/train_step.py:build_trainer"
        assert mon.site_compiles().get(ts, 0) >= 2  # init + step
    finally:
        if not was and not jitcheck.enabled_by_env():
            jitcheck.uninstall()
