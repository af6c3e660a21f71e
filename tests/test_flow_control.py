"""Flow control at the data plane (VERDICT r2 item 9).

Round 2 recorded per-worker backpressure (heartbeat ``flow`` surfaced in
coordinator stats) but nothing acted on it. Now the same signal rides each
FetchRequest (``flow_present``/``flow``: the consumer's prefetch-queue
depth; 0 = starving) and the shard server paces well-fed streams while a
starved stream is in flight — bandwidth shifts to the consumer that is
actually blocked on input.
"""

import socket
import threading
import time

import numpy as np
import pytest

from serverless_learn_tpu.control.client import ShardClient
from serverless_learn_tpu.control.daemons import start_shard_server


@pytest.fixture()
def shard_server(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = start_shard_server(port=port, root=str(tmp_path))
    yield f"127.0.0.1:{port}"
    proc.terminate()
    proc.wait(timeout=5)


BLOB = "synthetic:33554432"  # 32 MB, server-side generated


def _timed_fetch(addr, flow, out, key_idx):
    c = ShardClient(addr)
    try:
        c.set_flow(flow)
        t0 = time.perf_counter()
        data = c.fetch(BLOB)
        out[key_idx] = (time.perf_counter() - t0, len(data))
    finally:
        c.close()


def _contended(addr, probe_flow, other_flow, n_others=3):
    """One probe fetch vs ``n_others`` competitors, all concurrent 32 MB.
    Returns (probe_s, [other_s...])."""
    out = {}
    ts = [threading.Thread(target=_timed_fetch,
                           args=(addr, probe_flow, out, "probe"))]
    ts += [threading.Thread(target=_timed_fetch,
                            args=(addr, other_flow, out, f"o{i}"))
           for i in range(n_others)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert all(v[1] == 33554432 for v in out.values())
    return out["probe"][0], [out[f"o{i}"][0] for i in range(n_others)]


def test_fetch_carries_flow(shard_server):
    """The transport marks its fetches; the server's stats prove the
    starved stream was recognized."""
    c = ShardClient(shard_server)
    try:
        c.set_flow(0)
        assert len(c.fetch("synthetic:1000000")) == 1000000
        c.set_flow(None)
        assert len(c.fetch("synthetic:1000000")) == 1000000
    finally:
        c.close()
    probe = ShardClient(shard_server)
    try:
        stats = probe.stats()
        assert stats.starved_streams_served >= 1
    finally:
        probe.close()


@pytest.mark.slow
def test_starved_stream_prioritized_under_contention(shard_server):
    """The done-criterion: a starved worker's fetch latency drops under
    contention once flow is reported. Measurements (1 probe vs 3
    competitors, 32 MB each):

    1. everyone unreported -> symmetric baseline for the probe
    2. probe starved (0) vs well-fed (8) competitors -> the probe
       finishes ahead of every competitor and faster than its own
       symmetric baseline (median of 3 trials: absolute localhost
       timings are noisy; the ORDERING is the contract)
    """
    _contended(shard_server, None, None)  # warm server + page cache
    # INTERLEAVE baseline and starved trials: the two medians must see the
    # same external machine load, or a box-wide load swing between the
    # baseline block and the trial block fails the comparison spuriously
    # (observed once under a fully contended core).
    baselines, trials = [], []
    for _ in range(3):
        baselines.append(_contended(shard_server, None, None)[0])
        trials.append(_contended(shard_server, 0, 8))
    baseline = sorted(baselines)[1]
    starved = sorted(t[0] for t in trials)[1]
    # Every trial: the starved probe beats every well-fed competitor.
    for probe_s, others in trials:
        assert probe_s < min(others), (probe_s, others)
    # And the median beats the symmetric-contention baseline: the signal
    # moved real bandwidth, not just reordered bookkeeping.
    assert starved < baseline, (starved, baseline)

    probe = ShardClient(shard_server)
    try:
        stats = probe.stats()
        assert stats.throttled_chunks > 0
        assert stats.starved_streams_served >= 1
    finally:
        probe.close()


def test_shard_stream_source_reports_queue_depth(shard_server, monkeypatch):
    """The training input pipeline wires its prefetch-queue depth into the
    fetches it issues."""
    from serverless_learn_tpu.data.shard_client import (
        ShardStreamSource, publish_dataset)

    rng = np.random.default_rng(0)
    publish_dataset(shard_server, "ds", {
        "x": rng.standard_normal((64, 8)).astype(np.float32)},
        records_per_shard=16)
    flows = []
    real = ShardClient.set_flow

    def spy(self, flow):
        flows.append(flow)
        return real(self, flow)

    monkeypatch.setattr(ShardClient, "set_flow", spy)
    src = ShardStreamSource(shard_server, "ds", batch_size=8)
    it = iter(src)
    for _ in range(4):
        next(it)
    src.close()
    assert flows, "fetches must carry the queue depth"
    assert all(isinstance(f, int) and f >= 0 for f in flows)
