"""Runtime compile monitoring: the dynamic half of SLT010-SLT013.

The static rules prove what the AST shows; this module records what XLA
actually DOES. Opt-in via ``SLT_JITCHECK=1`` (the lockcheck/racecheck
idiom): ``install()`` — called from ``tests/conftest.py`` before the
package imports — replaces ``jax.jit`` with a factory that returns
instrumented wrappers for every jit the package creates. Each wrapper
reports to a process-global :class:`JitMonitor`:

* **every real compilation** (detected as ``_cache_size()`` growth
  across a call): creation site, abstract arg shapes/dtypes, donation
  mask, elapsed wall time, the triggering stack;
* **compile budgets**: ``declare_budget(site, max_compiles_per_jit=N)``
  lives NEXT TO the bucket functions (``continuous.py``,
  ``train_step.py``); a declared site whose jit object compiles more
  than N times is a violation — the memoized-bucket contract
  (``_paged_chunk_jit(nb, W)`` compiles exactly once per key) machine-
  checked;
* **frozen windows**: ``with jitcheck.frozen("post-warmup")`` marks a
  region (after ``warm_shapes()``, inside a measured bench window)
  where ANY compile is a violation — the surprise-recompile flake,
  caught with the stack that caused it instead of a mysterious p99;
* **donated-buffer reuse**: every concrete array leaf passed at a
  donated position is registered (id + weakref); a later call that
  passes a still-alive donated leaf is the round-15 "Array has been
  deleted" crash — detected LOGICALLY, which is the point: CPU ignores
  donation, so this fires on the parity tier for a bug that otherwise
  only detonates on a TPU.

Like lockcheck (exit 3) and racecheck (exit 4), violations fail the
pytest session — ``conftest.pytest_sessionfinish`` prints ``report()``
and exits 5. With ``SLT_JITCHECK_LOG=path`` every event is appended as
JSONL; ``replay_log()`` re-derives the verdicts offline and ``slt jit
LOG`` (exit 2 on violations) is the CI/forensics entry point, with
``slt jit --self-check`` validating the detector against synthetic
logs.

``bucket`` is also exported here: a zero-cost marker decorator
(``@jitcheck.bucket`` on ``_bucket``/``_wbucket``) that declares "this
function quantizes shape keys" — SLT012 reads the decorator statically
to separate bucket-derived jit-factory call sites from raw ``len()``
chains. This module imports jax lazily: importing ``jitcheck`` for the
decorator costs nothing on toolchain-less nodes.
"""

from __future__ import annotations

import json
import os
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

ENV_VAR = "SLT_JITCHECK"
LOG_ENV = "SLT_JITCHECK_LOG"
_STACK_DEPTH = 10
_SELF = os.path.abspath(__file__)

# Only jits CREATED from files whose path contains one of these are
# instrumented — same rationale as lockcheck.DEFAULT_SCOPE: the
# invariant under test is this package's compile discipline, not jax's
# internal jits.
DEFAULT_SCOPE = ("serverless_learn_tpu", "tests")


class JitCheckViolation(AssertionError):
    """A compile budget was exceeded, a frozen window compiled, or a
    donated buffer was reused."""


def bucket(fn):
    """Marker: ``fn`` quantizes raw sizes into a closed bucket set.

    Zero runtime cost; SLT012 reads the decorator off the AST to decide
    whether a jit-factory call site derives its shape key from a
    declared bucket function or a raw ``len()`` chain."""
    fn.__slt_bucket__ = True
    return fn


# -- site / stack helpers ----------------------------------------------------


def _frames():
    return traceback.extract_stack()[:-2]


def _site(scope=DEFAULT_SCOPE) -> Optional[str]:
    """``relpath:funcname`` of the first in-scope caller frame; None
    when the jit is created outside the scope (left uninstrumented)."""
    for frame in reversed(_frames()):
        path = os.path.abspath(frame.filename)
        if path == _SELF or "jax/" in path or "jax\\" in path:
            continue
        hit = None
        for s in scope:
            idx = path.find(os.sep + s + os.sep)
            if idx >= 0:
                hit = path[idx + 1:]
                break
            if os.path.basename(os.path.dirname(path)) == s:
                hit = os.path.join(s, os.path.basename(path))
                break
        if hit is None:
            return None
        return f"{hit}:{frame.name}"
    return None


def _stack() -> List[str]:
    out = []
    for frame in _frames():
        if os.path.abspath(frame.filename) == _SELF:
            continue
        out.append(f"{frame.filename}:{frame.lineno} in {frame.name}")
    return out[-_STACK_DEPTH:]


def _abstract(args: tuple) -> List[str]:
    """Compact ``dtype[shape]`` summaries of each arg's leaves."""
    import jax

    out = []
    for a in args:
        leaves = jax.tree_util.tree_leaves(a)
        parts = []
        for leaf in leaves[:8]:
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None:
                parts.append(type(leaf).__name__)
            else:
                parts.append(f"{dtype}{list(shape)}")
        if len(leaves) > 8:
            parts.append(f"...+{len(leaves) - 8}")
        out.append(",".join(parts) or "()")
    return out


# -- the monitor -------------------------------------------------------------


class JitMonitor:
    """Process-global record of compiles, budgets, frozen windows, and
    the donated-buffer registry."""

    def __init__(self, name: str = "default",
                 log_path: Optional[str] = None):
        self.name = name
        self._mu = threading.RLock()
        self._records: List[dict] = []      # every compile event
        self._violations: List[dict] = []
        self._budgets: Dict[str, int] = {}
        self._site_compiles: Dict[str, int] = {}
        # Frozen windows are GLOBAL, not thread-local: the continuous
        # engine compiles on its dispatcher thread while the test
        # thread holds the freeze.
        self._frozen: List[str] = []
        # id(leaf) -> (weakref, donation record). The weakref guards
        # id reuse: a dead entry is vacuously safe.
        self._donated: Dict[int, tuple] = {}
        self._log_path = log_path
        self._log_fh = None

    # -- logging -----------------------------------------------------------

    def _log(self, ev: dict):
        if self._log_path is None:
            return
        line = json.dumps(ev) + "\n"
        # Open OUTSIDE the mutex (SLT001: no filesystem I/O under a
        # lock the compile path contends on); the benign double-open
        # race just wastes one fd, which close_log() reaps.
        if self._log_fh is None:
            fh = open(self._log_path, "a", encoding="utf-8")
            with self._mu:
                if self._log_fh is None:
                    self._log_fh = fh
                else:
                    fh.close()
        with self._mu:
            self._log_fh.write(line)
            self._log_fh.flush()

    def close_log(self):
        with self._mu:
            if self._log_fh is not None:
                self._log_fh.close()
                self._log_fh = None

    # -- declarations ------------------------------------------------------

    def declare_budget(self, site: str, max_compiles_per_jit: int = 1):
        with self._mu:
            self._budgets[site] = max_compiles_per_jit
        self._log({"ev": "declare", "site": site,
                   "budget": max_compiles_per_jit})

    def budget_for(self, site: Optional[str]) -> Optional[int]:
        with self._mu:
            return self._budgets.get(site) if site else None

    # -- frozen windows ----------------------------------------------------

    def freeze(self, label: str):
        with self._mu:
            self._frozen.append(label)
        self._log({"ev": "freeze", "label": label})

    def thaw(self, label: str):
        with self._mu:
            if label in self._frozen:
                self._frozen.remove(label)
        self._log({"ev": "thaw", "label": label})

    def frozen_label(self) -> Optional[str]:
        with self._mu:
            return self._frozen[-1] if self._frozen else None

    # -- compile events ----------------------------------------------------

    def on_compile(self, site: str, obj_compiles: int, args: tuple,
                   donate: tuple, elapsed: float):
        frozen = self.frozen_label()
        rec = {
            "ev": "compile", "site": site, "n": obj_compiles,
            "args": _abstract(args), "donate": list(donate),
            "elapsed_ms": round(elapsed * 1e3, 3), "frozen": frozen,
            "stack": _stack(),
        }
        budget = self.budget_for(site)
        with self._mu:
            self._records.append(rec)
            self._site_compiles[site] = \
                self._site_compiles.get(site, 0) + 1
        self._log(rec)
        if frozen is not None:
            self._violation({
                "kind": "frozen", "site": site, "label": frozen,
                "stack": rec["stack"], "args": rec["args"],
                "why": f"compile at {site} inside frozen window "
                       f"{frozen!r}: post-warmup recompile — the shape "
                       f"key escaped warm_shapes()' closed set",
            })
        if budget is not None and obj_compiles > budget:
            self._violation({
                "kind": "budget", "site": site, "budget": budget,
                "compiles": obj_compiles, "stack": rec["stack"],
                "args": rec["args"],
                "why": f"jit created at {site} compiled "
                       f"{obj_compiles}x against a declared budget of "
                       f"{budget} per jit object: the memoized-bucket "
                       f"contract is broken (a key leaked past its "
                       f"cache)",
            })

    # -- donation registry -------------------------------------------------

    def note_donated(self, site: str, args: tuple, donate: tuple):
        import weakref

        import jax

        with self._mu:
            for i in donate:
                if i >= len(args):
                    continue
                for leaf in jax.tree_util.tree_leaves(args[i]):
                    if not isinstance(leaf, jax.Array) or isinstance(
                            leaf, jax.core.Tracer):
                        continue
                    key = id(leaf)
                    try:
                        ref = weakref.ref(
                            leaf,
                            lambda _, k=key: self._donated.pop(k, None))
                    except TypeError:
                        continue
                    self._donated[key] = (ref, {
                        "site": site, "arg": i, "stack": _stack()})

    def check_reuse(self, site: str, args: tuple):
        import jax

        hits = []
        with self._mu:
            for a in args:
                for leaf in jax.tree_util.tree_leaves(a):
                    if isinstance(leaf, jax.core.Tracer):
                        continue
                    entry = self._donated.get(id(leaf))
                    if entry is not None and entry[0]() is leaf:
                        hits.append(entry[1])
                        del self._donated[id(leaf)]
        for donated in hits:
            ev = {"ev": "donation_reuse", "site": site,
                  "donated": donated, "stack": _stack()}
            self._log(ev)
            self._violation({
                "kind": "donation_reuse", "site": site,
                "donated": donated, "stack": ev["stack"],
                "why": f"argument passed to {site} was donated to "
                       f"{donated['site']} (arg {donated['arg']}) and "
                       f"never rebound: on TPU this is 'Array has been "
                       f"deleted' — CPU merely masks it",
            })

    def _violation(self, v: dict):
        with self._mu:
            self._violations.append(v)
        self._log({"ev": "violation", **v})

    # -- read side ---------------------------------------------------------

    def records(self) -> List[dict]:
        with self._mu:
            return list(self._records)

    def violations(self) -> List[dict]:
        with self._mu:
            return list(self._violations)

    def site_compiles(self) -> Dict[str, int]:
        with self._mu:
            return dict(self._site_compiles)

    def reset(self):
        with self._mu:
            self._records.clear()
            self._violations.clear()
            self._site_compiles.clear()
            self._donated.clear()
            self._frozen.clear()

    def report(self) -> str:
        vio = self.violations()
        sites = self.site_compiles()
        lines = [f"jitcheck[{self.name}]: {sum(sites.values())} "
                 f"compile(s) across {len(sites)} site(s), "
                 f"{len(vio)} violation(s)"]
        for site, n in sorted(sites.items()):
            budget = self.budget_for(site)
            suffix = f" (budget {budget}/jit)" if budget else ""
            lines.append(f"  {site}: {n} compile(s){suffix}")
        for v in vio:
            lines.append(f"  VIOLATION [{v['kind']}] {v['why']}")
            for fr in v.get("stack", [])[-5:]:
                lines.append(f"    {fr}")
            donated = v.get("donated")
            if donated:
                lines.append("   donated at:")
                for fr in donated.get("stack", [])[-5:]:
                    lines.append(f"    {fr}")
        return "\n".join(lines)

    def assert_clean(self):
        if self.violations():
            raise JitCheckViolation(self.report())


# -- the wrapper -------------------------------------------------------------


class _InstrumentedJit:
    """Duck-typed stand-in for a jitted callable reporting compiles
    (cache-size growth) and donation traffic to the CURRENT monitor —
    looked up per call, so tests can retarget with :func:`scoped`
    without re-wrapping."""

    def __init__(self, inner, site: str, donate: tuple):
        self._inner = inner
        self.site = site
        self._donate = donate
        self._compiles = 0

    def _cache_size(self):
        try:
            return self._inner._cache_size()
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        mon = monitor()
        mon.check_reuse(self.site, args)
        before = self._cache_size()
        t0 = time.perf_counter()
        out = self._inner(*args, **kwargs)
        elapsed = time.perf_counter() - t0
        after = self._cache_size()
        if before is not None and after is not None and after > before:
            self._compiles += after - before
            mon.on_compile(self.site, self._compiles, args,
                           self._donate, elapsed)
        if self._donate:
            mon.note_donated(self.site, args, self._donate)
        return out

    def __getattr__(self, name):
        # lower()/trace()/eval_shape() etc. pass through uncounted:
        # an explicit AOT lower is a decision, not a surprise.
        return getattr(self._inner, name)

    def __repr__(self):
        return f"<jitcheck-instrumented {self._inner!r} from {self.site}>"


# -- global install ----------------------------------------------------------

_default_monitor = JitMonitor()
_active_monitor: Optional[JitMonitor] = None
_installed = False
_real_jit = None


def monitor() -> JitMonitor:
    return _active_monitor if _active_monitor is not None \
        else _default_monitor


class scoped:
    """Route wrapper events to a LOCAL monitor for one with-block (test
    isolation under a global SLT_JITCHECK=1 install)."""

    def __init__(self, mon: JitMonitor):
        self._mon = mon
        self._prev: Optional[JitMonitor] = None

    def __enter__(self):
        global _active_monitor
        self._prev = _active_monitor
        _active_monitor = self._mon
        return self._mon

    def __exit__(self, *exc):
        global _active_monitor
        _active_monitor = self._prev
        return False


class frozen:
    """``with jitcheck.frozen("measured-window"):`` — any compile inside
    is a violation. Reentrant; global across threads by design."""

    def __init__(self, label: str):
        self.label = label

    def __enter__(self):
        monitor().freeze(self.label)
        return self

    def __exit__(self, *exc):
        monitor().thaw(self.label)
        return False


def declare_budget(site: str, max_compiles_per_jit: int = 1):
    """Module-level declaration, placed next to the bucket functions.

    No-op overhead when the monitor never sees the site; under
    SLT_JITCHECK=1 a jit object created at ``site`` that compiles more
    than the budget fails the session."""
    _default_monitor.declare_budget(site, max_compiles_per_jit)


def enabled_by_env() -> bool:
    return os.environ.get(ENV_VAR, "") == "1"


def install(scope=DEFAULT_SCOPE) -> JitMonitor:
    """Patch ``jax.jit`` so every in-scope jit created AFTER this call
    is instrumented. Idempotent; must run before the package imports
    (decorator-time ``@jax.jit`` binds at module import)."""
    global _installed, _real_jit
    if _installed:
        return _default_monitor
    import jax

    _real_jit = jax.jit
    log_path = os.environ.get(LOG_ENV) or None
    if log_path:
        _default_monitor._log_path = log_path

    def _jit(fun=None, *rest, **kwargs):
        inner = _real_jit(fun, *rest, **kwargs)
        site = _site(scope)
        if site is None:
            return inner
        donate = kwargs.get("donate_argnums", ())
        if isinstance(donate, int):
            donate = (donate,)
        try:
            donate = tuple(int(i) for i in donate)
        except TypeError:
            donate = ()
        _default_monitor._log({"ev": "jit", "site": site,
                               "donate": list(donate)})
        return _InstrumentedJit(inner, site, donate)

    jax.jit = _jit
    _installed = True
    return _default_monitor


def uninstall():
    global _installed
    if _installed:
        import jax

        jax.jit = _real_jit
        _installed = False


def installed() -> bool:
    return _installed


# -- offline replay ----------------------------------------------------------


def replay_log(path: str) -> dict:
    """Re-derive verdicts from a ``SLT_JITCHECK_LOG`` JSONL file.

    Deterministic: budgets, freeze/thaw nesting and per-site compile
    counts are rebuilt from the event stream, so a CI node without jax
    can audit a log a TPU run produced. Returns ``{"compiles", "sites",
    "violations", "events"}`` — recorded ``violation`` events are
    cross-checked against the re-derivation, and any violation the
    stream SHOULD have produced but did not record is added (a
    truncated log still convicts)."""
    budgets: Dict[str, int] = {}
    frozen_stack: List[str] = []
    site_compiles: Dict[str, int] = {}
    violations: List[dict] = []
    recorded: List[dict] = []
    compiles = 0
    events = 0

    def add(v: dict):
        for have in violations:
            if have.get("kind") == v.get("kind") \
                    and have.get("site") == v.get("site") \
                    and have.get("n") == v.get("n"):
                return
        violations.append(v)

    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            ev = json.loads(line)
            events += 1
            kind = ev.get("ev")
            if kind == "declare":
                budgets[ev["site"]] = int(ev["budget"])
            elif kind == "freeze":
                frozen_stack.append(ev.get("label", "?"))
            elif kind == "thaw":
                if ev.get("label") in frozen_stack:
                    frozen_stack.remove(ev["label"])
            elif kind == "compile":
                compiles += 1
                site = ev.get("site", "?")
                site_compiles[site] = site_compiles.get(site, 0) + 1
                n = int(ev.get("n", 1))
                if frozen_stack or ev.get("frozen"):
                    add({"kind": "frozen", "site": site, "n": n,
                         "label": ev.get("frozen")
                         or frozen_stack[-1],
                         "stack": ev.get("stack", [])})
                budget = budgets.get(site)
                if budget is not None and n > budget:
                    add({"kind": "budget", "site": site, "n": n,
                         "budget": budget,
                         "stack": ev.get("stack", [])})
            elif kind == "donation_reuse":
                add({"kind": "donation_reuse",
                     "site": ev.get("site", "?"),
                     "donated": ev.get("donated", {}),
                     "stack": ev.get("stack", [])})
            elif kind == "violation":
                recorded.append(ev)

    return {"compiles": compiles, "sites": site_compiles,
            "violations": violations, "recorded": recorded,
            "events": events}


def self_check() -> List[str]:
    """Validate the replay verdict engine against synthetic logs.

    Returns a list of failure strings (empty = pass): a clean log must
    produce zero violations; seeded budget-exceed, frozen-compile and
    donation-reuse streams must each be convicted."""
    import tempfile

    failures: List[str] = []

    def _run(events: List[dict]) -> dict:
        with tempfile.NamedTemporaryFile(
                "w", suffix=".jsonl", delete=False) as fh:
            for ev in events:
                fh.write(json.dumps(ev) + "\n")
            path = fh.name
        try:
            return replay_log(path)
        finally:
            os.unlink(path)

    site = "serverless_learn_tpu/inference/continuous.py:_paged_chunk_jit"
    clean = _run([
        {"ev": "declare", "site": site, "budget": 1},
        {"ev": "compile", "site": site, "n": 1, "args": ["f32[8]"]},
        {"ev": "freeze", "label": "w"},
        {"ev": "thaw", "label": "w"},
        {"ev": "compile", "site": site, "n": 1, "args": ["f32[16]"]},
    ])
    if clean["violations"]:
        failures.append(f"clean log convicted: {clean['violations']}")

    over = _run([
        {"ev": "declare", "site": site, "budget": 1},
        {"ev": "compile", "site": site, "n": 2, "args": ["f32[8]"]},
    ])
    if not any(v["kind"] == "budget" for v in over["violations"]):
        failures.append("budget overrun not detected")

    froz = _run([
        {"ev": "freeze", "label": "measured"},
        {"ev": "compile", "site": site, "n": 1, "args": ["f32[8]"]},
    ])
    if not any(v["kind"] == "frozen" for v in froz["violations"]):
        failures.append("frozen-window compile not detected")

    reuse = _run([
        {"ev": "donation_reuse", "site": site,
         "donated": {"site": site, "arg": 1}},
    ])
    if not any(v["kind"] == "donation_reuse"
               for v in reuse["violations"]):
        failures.append("donation reuse not detected")

    return failures
