"""shard_map helpers shared by every shard_map user in the tree."""

from __future__ import annotations

from jax import shard_map


def shard_map_no_check(fn, *, mesh, in_specs, out_specs):
    """shard_map with replication checking off."""
    return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


# -- manual-region tracking -------------------------------------------------
#
# Ops that wrap themselves in shard_map against the active mesh (flash
# attention, fused cross-entropy) must NOT do so when already executing
# inside another shard_map over that mesh (e.g. a GPipe pipeline stage) —
# nesting raises "context mesh should match" at trace time, and inside the
# outer region the data is already device-local, so running the op's plain
# local path is exactly right. The framework's shard_map entry points mark
# their dynamic extent here.

import contextlib
import threading

_MANUAL = threading.local()


@contextlib.contextmanager
def manual_region():
    prev = getattr(_MANUAL, "depth", 0)
    _MANUAL.depth = prev + 1
    try:
        yield
    finally:
        _MANUAL.depth = prev


def in_manual_region() -> bool:
    return getattr(_MANUAL, "depth", 0) > 0
