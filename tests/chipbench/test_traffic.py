"""``traffic.py``: determinism per seed, the same work for every seed,
due-time timing and the generator's lateness."""

import socket
import threading
import time
import json

import numpy as np
import pytest

from chipbench import traffic
from chipbench.drivers import serve

MIX = {"pool": 64, "pool_seed": 7, "rate_per_s": 50.0, "warm_in_s": 0.2,
       "prompt_tokens": {"median": 40, "sigma": 0.6, "min": 8, "max": 96},
       "output_tokens": {"median": 12, "sigma": 0.6, "min": 2, "max": 40},
       "shared_prefix": {"count": 2, "tokens": 16, "share": 0.5}}


def test_same_seed_same_inputs_and_large_seeds_work():
    big = 2 ** 31 + 12345
    a, b = (traffic.request_mix(MIX, big, 1000) for _ in range(2))
    assert a == b
    assert a != traffic.request_mix(MIX, big + 1, 1000)
    x = next(traffic.train_batches(big, 1000, 2, 16))["tokens"]
    y = next(traffic.train_batches(big, 1000, 2, 16))["tokens"]
    assert (x == y).all() and x.dtype == np.int32
    assert not (x[0] == x[1]).all()      # rows all differ


@pytest.mark.parametrize("seed", [1, 99, 2 ** 31 + 5])
def test_every_seed_gets_the_same_lengths_in_another_order(seed):
    key = lambda m: sorted((len(r["prompt"]), r["max_new_tokens"],
                            r["shared_prefix"] is not None) for r in m)
    base = traffic.request_mix(MIX, 0, 1000)
    other = traffic.request_mix(MIX, seed, 1000)
    assert key(base) == key(other)
    assert [len(r["prompt"]) for r in base] != [len(r["prompt"])
                                                for r in other]
    p = MIX["prompt_tokens"]
    assert all(p["min"] <= len(r["prompt"]) <= p["max"] for r in other)


def test_shared_prefixes_are_shared_and_keep_the_drawn_length():
    mix = traffic.request_mix(MIX, 3, 1000)
    by = {}
    for r in mix:
        if r["shared_prefix"] is not None:
            by.setdefault(r["shared_prefix"], set()).add(
                tuple(r["prompt"][:16]))
    assert by and all(len(heads) == 1 for heads in by.values())


@pytest.mark.parametrize("seed", [0, 5, 2 ** 31 + 9])
def test_every_seed_offers_the_same_load(seed):
    warm, due = traffic.poisson_schedule(MIX, seed, 2.0)
    assert len(due) == 100 and due[0] == 0.0 and max(due) < 2.0
    assert due == sorted(due)
    assert all(-0.2 <= t <= 0 for t in warm) and len(warm) == 10
    gaps = sorted(np.diff(due + [2.0]))
    base = sorted(np.diff(traffic.poisson_schedule(MIX, 1, 2.0)[1] + [2.0]))
    assert np.allclose(gaps, base)


class _SlowServer:
    """Answers each JSON line after ``delay_s``, ONE connection at a time:
    a stalled server, so that later requests wait behind earlier ones."""

    def __init__(self, delay_s):
        self.delay_s = delay_s
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(64)
        self.addr = "127.0.0.1:%d" % self.sock.getsockname()[1]
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._loop, daemon=True)
        self.thread.start()

    def _loop(self):
        self.sock.settimeout(0.1)
        while not self.stop.is_set():
            try:
                conn, _ = self.sock.accept()
            except socket.timeout:
                continue
            with conn, conn.makefile("rwb") as f:
                req = json.loads(f.readline())
                time.sleep(self.delay_s)
                f.write(json.dumps({"new_tokens": [1] * req[
                    "max_new_tokens"]}).encode() + b"\n")
                f.flush()

    def close(self):
        self.stop.set()
        self.thread.join(timeout=5)
        self.sock.close()


def test_open_loop_times_each_request_from_when_it_was_due():
    srv = _SlowServer(0.05)
    try:
        mix = [{"prompt": [1, 2], "max_new_tokens": 2,
                "shared_prefix": None}] * 8
        due = [0.0, 0.01, 0.02, 0.03]          # faster than the server
        records, t_open, t_close, late = serve.open_loop(
            srv.addr, mix, [], due, seconds=0.4, workers=4,
            on_open=lambda: None, on_close=lambda: None)
    finally:
        srv.close()
    assert all("new_tokens" in r for r in records)
    waits = [r["arrived"] - r["due"] for r in records]
    # The server answers one at a time: the 4th request waited for the
    # three before it, and that wait is IN its latency.
    assert waits[3] > 0.17 and waits == sorted(waits)
    assert [round(r["due"] - t_open, 3) for r in records] == due
    # The generator itself was on time, and says so.
    summary = traffic.lateness_summary(late)
    assert summary["n"] == 4 and summary["max_ms"] < 50.0


def test_lateness_is_reported_when_the_generator_runs_late():
    assert traffic.lateness_summary([]) == {"n": 0, "p50_ms": None,
                                            "max_ms": None}
    s = traffic.lateness_summary([0.001, 0.002, 0.5])
    assert s["max_ms"] == pytest.approx(500.0) and s["p50_ms"] == \
        pytest.approx(2.0)


def test_a_request_that_never_answers_enters_at_its_timeout(tiny_root):
    from chipbench.cell import load_cell

    cell = load_cell("tiny-steady", tiny_root)
    item = {"prompt": [1], "max_new_tokens": 10, "shared_prefix": None}
    ok = {"item": item, "due": 1.0, "arrived": 1.5, "in_window": True,
          "new_tokens": [0] * 10}
    lost = {"item": item, "due": 1.2, "in_window": True}
    rec = serve._account(cell, [ok, lost], 1.0, 2.0, 1.0, [])
    assert rec["attempted"] == 2 and rec["failed"] == 1
    assert rec["never_came"] == 1
    worst = serve.REPLY_TIMEOUT_S * 1e3 / 10
    assert rec["end_to_end"]["serve_norm_latency_p95"] > 0.9 * worst


def test_a_length_cycle_gives_every_stretch_of_the_mix_the_same_work():
    params = dict(MIX, pool=64, length_cycle=8)
    params.pop("shared_prefix")
    mix = traffic.request_mix(params, 4, 1000)
    shape = lambda r: (len(r["prompt"]), r["max_new_tokens"])
    cycle = [shape(r) for r in mix[:8]]
    assert [shape(r) for r in mix] == cycle * 8
    # ...the same pairs in the same order for every seed (the order is
    # work too), with other tokens, which never repeat (no prefix is
    # shared by accident).
    other = traffic.request_mix(params, 5, 1000)
    assert cycle == [shape(r) for r in other[:8]]
    assert mix[0]["prompt"] != other[0]["prompt"]
    assert len({tuple(r["prompt"][:8]) for r in mix}) == 64


class _EchoServer(_SlowServer):
    """Persistent connections, one thread each, ``delay_s`` per reply."""

    def _loop(self):
        self.sock.settimeout(0.1)
        while not self.stop.is_set():
            try:
                conn, _ = self.sock.accept()
            except socket.timeout:
                continue
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn):
        with conn, conn.makefile("rwb") as f:
            for line in f:
                time.sleep(self.delay_s)
                f.write(json.dumps({"new_tokens": [1] * json.loads(line)[
                    "max_new_tokens"]}).encode() + b"\n")
                f.flush()


def test_closed_loop_window_is_cut_at_reply_boundaries_and_whole_cycles(
        tiny_root):
    from chipbench.cell import load_cell

    srv = _EchoServer(0.01)
    marks = []
    try:
        mix = [{"prompt": [1], "max_new_tokens": 1 + i % 5,
                "shared_prefix": None} for i in range(2000)]
        records, t_open, t_close = serve.closed_loop(
            srv.addr, mix, clients=3, warm_in_replies=7, cycle=5,
            seconds=0.3, on_open=lambda: marks.append("open"),
            on_close=lambda: marks.append("close"))
    finally:
        srv.close()
    assert marks == ["open", "close"]
    arrivals = sorted(r["arrived"] for r in records if "arrived" in r)
    assert t_open == arrivals[6]            # the reply that ends the warm-in
    assert t_close in arrivals and t_close - t_open >= 0.3
    inside = [a for a in arrivals if t_open < a <= t_close]
    assert len(inside) % 5 == 0 and len(inside) >= 5
    # The rate is over the replies inside and the time that really passed.
    cell = load_cell("tiny-backlog", tiny_root)
    rec = serve._account(cell, records, t_open, t_close, 0.3, [])
    tokens = sum(len(r["new_tokens"]) for r in records
                 if t_open < r.get("arrived", 0) <= t_close)
    assert rec["end_to_end"]["serve_tokens_per_s"] == pytest.approx(
        tokens / (t_close - t_open))
    assert rec["window_s"] == pytest.approx(t_close - t_open)
