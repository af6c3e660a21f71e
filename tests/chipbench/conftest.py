"""Fixtures of the benchmark's own tests (tier-1 collects this
directory; ``tests/conftest.py`` has already held JAX to the CPU)."""

import os
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(os.path.dirname(_HERE))
for p in (_ROOT, _HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    import tiny

    return tiny.write_tree(str(tmp_path_factory.mktemp("tiny_bench")))


@pytest.fixture
def no_compile_cache(monkeypatch):
    """The harness places a persistent compile cache; tests must not
    write one into a temporary root."""
    from chipbench import run

    monkeypatch.setattr(run, "place_caches", lambda root: None)
