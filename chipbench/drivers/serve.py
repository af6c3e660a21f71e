"""The serving windows: a ``GenerationServer`` built as ``serve`` builds
it, started in THIS process (only the process that holds the chip can
trace it), driven over its JSON-lines wire by client threads.

``serve_closed`` keeps a fixed number of requests outstanding (an offline
job); ``serve_open`` sends on a Poisson schedule whatever the server does
(independent users) and times each request from when it was DUE.
"""

from __future__ import annotations

import json
import queue
import socket
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import checks, reference, traffic, weights

REPLY_TIMEOUT_S = 120.0     # a request that has not answered by then failed
DRAIN_S = 60.0              # how long past the close an answer is waited for


class SpanSink:
    """In-memory stand-in for the engine's JSONL event log."""

    def __init__(self):
        self.records = []

    def emit(self, rec: dict) -> None:
        self.records.append(rec)


def _weights(cell, seed: int, dtype):
    return cell.arch.to_program_tree(weights.make_weights(
        cell.arch, cell.sizes, weights.seed_u32(seed), dtype))


def build(cell, seed: int, session: dict = None):
    """(server, sizes) — ``_serving_config`` -> ``_build_inference_trainer``
    as ``cmd_serve`` does; the weights are the benchmark's, from the seed.
    ``session`` (``control.py``: many seeds in one process) keeps the
    server with its warmed programs and swaps the next seed's weights in
    through the engine's own ``set_params``."""
    from serverless_learn_tpu import cli
    from serverless_learn_tpu.config import ExperimentConfig
    from serverless_learn_tpu.inference.server import GenerationServer

    cfg = cli._serving_config(ExperimentConfig.from_dict(
        cell.program_config()))
    sz = cell.sizes
    dtype = jnp.dtype(cfg.train.param_dtype)
    if session is not None and "server" in session:
        server = session["server"]
        server.engine.params = server.params = None   # room for the next
        params = _weights(cell, seed, dtype)
        server.params = params
        server.engine.set_params(params)
        return server, sz
    trainer = cli._build_inference_trainer(cfg)
    module = trainer.bundle.module
    params = _weights(cell, seed, dtype)
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    weights.check_tree_matches(params, abstract)
    serve = cell.config.get("serve", {})
    server = GenerationServer(
        module, params, host="127.0.0.1", port=0,
        max_batch=serve.get("max_batch", 8),
        chunk_size=serve.get("chunk_size", 32), kv=cfg.kv,
        event_sink=SpanSink())
    server.start()
    if session is not None:
        session["server"] = server
    return server, sz


def release(session: dict) -> None:
    """Drop a session's weights: the reference's own have to fit beside
    the pool. The next seed's are swapped in by ``build``."""
    server = session.get("server")
    if server is not None:
        server.engine.params = server.params = None


class _Conn:
    """One JSON-lines connection to the server."""

    def __init__(self, addr: str):
        host, _, port = addr.rpartition(":")
        self.sock = socket.create_connection((host, int(port)),
                                             timeout=REPLY_TIMEOUT_S)
        self.f = self.sock.makefile("rwb")

    def ask(self, req: dict) -> dict:
        self.f.write(json.dumps(req).encode() + b"\n")
        self.f.flush()
        line = self.f.readline()
        if not line:
            raise ConnectionError("server closed the connection")
        return json.loads(line)

    def close(self):
        try:
            self.f.close()
            self.sock.close()
        except OSError:
            pass


def _one_request(conn, item: dict, rec: dict) -> None:
    """Send one request and fill ``rec``; never raises."""
    rec["sent"] = time.perf_counter()
    try:
        rep = conn.ask({"prompt": item["prompt"],
                        "max_new_tokens": item["max_new_tokens"],
                        "temperature": 0.0})
    except (OSError, ValueError) as e:
        rep = {"error": f"{type(e).__name__}: {e}"}
    rec["arrived"] = time.perf_counter()
    if "error" in rep:
        rec["error"] = str(rep["error"])
    else:
        rec["new_tokens"] = rep["new_tokens"]


MAX_OVERRUN_S = 40.0        # a closed-loop window closes by then regardless


def closed_loop(addr: str, mix: list, clients: int, warm_in_replies: int,
                cycle: int, seconds: float, on_open, on_close) -> tuple:
    """``clients`` threads, each sending its next request when the last
    one answered. Returns (records, t_open, t_close).

    The window is cut at reply boundaries, as the training window is cut
    at step boundaries: it opens with the arrival of the reply that ends
    the warm-in, and closes with the arrival of the first reply at or after
    ``seconds`` that completes a whole number of the mix's length cycles
    (``cycle`` replies each; 1 = any reply). Replies come whole, some
    thirty to a window: a window cut at a fixed instant counts one reply
    more or less by chance, and a part of a cycle holds other work than
    the whole. The rate divides by the time that really passed.
    """
    records, lock = [], threading.Lock()
    state = {"next": 0, "replies": 0, "in_window": 0,
             "t_open": None, "t_close": None}
    opened, closed, stop = (threading.Event(), threading.Event(),
                            threading.Event())

    def client():
        conn = _Conn(addr)
        try:
            while not stop.is_set():
                with lock:
                    i = state["next"]
                    state["next"] += 1
                    if i >= len(mix):
                        return
                    rec = {"i": i, "item": mix[i]}
                    records.append(rec)
                _one_request(conn, mix[i], rec)
                rec["due"] = rec["sent"]
                with lock:
                    state["replies"] += 1
                    if state["t_open"] is None:
                        if state["replies"] >= warm_in_replies:
                            state["t_open"] = rec["arrived"]
                            opened.set()
                    elif state["t_close"] is None:
                        state["in_window"] += 1
                        if (rec["arrived"] - state["t_open"] >= seconds
                                and state["in_window"] % cycle == 0):
                            state["t_close"] = rec["arrived"]
                            closed.set()
        finally:
            conn.close()

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for th in threads:
        th.start()
    if not opened.wait(timeout=600.0):
        stop.set()
        raise RuntimeError("warm-in never finished: no replies arrive")
    on_open()
    closed.wait(timeout=seconds + MAX_OVERRUN_S)
    with lock:
        if state["t_close"] is None:     # no cycle boundary came in time
            state["t_close"] = time.perf_counter()
    on_close()
    stop.set()
    deadline = time.perf_counter() + DRAIN_S
    for th in threads:
        th.join(timeout=max(0.1, deadline - time.perf_counter()))
    return records, state["t_open"], state["t_close"]


def open_loop(addr: str, mix: list, warm_due: list, window_due: list,
              seconds: float, workers: int, on_open, on_close) -> tuple:
    """Requests sent when DUE, whatever the server does: ``workers``
    threads wait on a queue; a scheduler thread hands each request over at
    its due time and notes how late it was. Returns (records, t_open,
    t_close, lateness of the hand-overs in seconds)."""
    if len(warm_due) + len(window_due) > len(mix):
        raise ValueError("the mix's pool is smaller than the schedule")
    q: queue.Queue = queue.Queue()
    records = []

    def worker():
        while True:
            rec = q.get()
            if rec is None:
                return
            conn = None
            try:
                conn = _Conn(addr)
                _one_request(conn, rec["item"], rec)
            except OSError as e:
                rec["arrived"] = time.perf_counter()
                rec["error"] = f"{type(e).__name__}: {e}"
            finally:
                if conn is not None:
                    conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(workers)]
    for th in threads:
        th.start()
    t_open = time.perf_counter() - (warm_due[0] if warm_due else 0.0)
    lateness = []
    opened = False
    for j, due in enumerate(list(warm_due) + list(window_due)):
        in_window = j >= len(warm_due)
        if in_window and not opened:
            time.sleep(max(0.0, t_open - time.perf_counter()))
            on_open()
            opened = True
        time.sleep(max(0.0, t_open + due - time.perf_counter()))
        rec = {"i": j, "item": mix[j], "due": t_open + due,
               "in_window": in_window}
        records.append(rec)
        q.put(rec)
        if in_window:
            lateness.append(time.perf_counter() - rec["due"])
    time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
    t_close = time.perf_counter()
    on_close()
    for _ in threads:
        q.put(None)
    deadline = t_close + DRAIN_S
    for th in threads:
        th.join(timeout=max(0.1, deadline - time.perf_counter()))
    return records, t_open, t_close, lateness


def _engine_counts(engine) -> dict:
    return {"chunks_run": engine.chunks_run,
            "decoded_rows": engine.decoded_rows_total,
            "dispatched_rows": engine.dispatched_rows_total,
            "prefill_chunks": engine.prefill_chunks_run,
            "requests_finished": engine.requests_finished,
            "preemptions": engine.preemptions,
            "t": time.perf_counter()}


def run(cell, seed: int, seconds: float, tracer, session: dict = None
        ) -> dict:
    t = cell.traffic
    warmed = session is not None and "server" in session
    server, sz = build(cell, seed, session)
    engine = server.engine
    try:
        n_programs = 0 if warmed else cell.arch.warm(engine, t)
        mix = traffic.request_mix(t, seed, sz.vocab)
        marks = {}

        def on_open():
            marks["c0"] = _engine_counts(engine)
            marks["span0"] = len(engine.event_log.records)
            tracer.window_opens()

        def on_close():
            # Counters and spans are read as the window closes, not after
            # the wait for the answers still in flight.
            marks["c1"] = _engine_counts(engine)
            marks["span1"] = len(engine.event_log.records)
            tracer.window_closes()

        lateness = []
        if cell.kind == "serve_closed":
            records, t_open, t_close = closed_loop(
                server.addr, mix, t["clients"], t["warm_in_replies"],
                t.get("length_cycle", 1), seconds, on_open, on_close)
        elif cell.kind == "serve_open":
            warm_due, window_due = traffic.poisson_schedule(t, seed, seconds)
            records, t_open, t_close, lateness = open_loop(
                server.addr, mix, warm_due, window_due, seconds,
                t["workers"], on_open, on_close)
        else:
            raise ValueError(f"unknown kind {cell.kind!r}")
        spans = list(engine.event_log.records)[
            marks["span0"]:marks["span1"]]
        kv = engine.kv_stats() or {}
        kv.pop("prefix_digest", None)
    finally:
        if session is None:
            server.stop()
    record = _account(cell, records, t_open, t_close, seconds, lateness)
    c0, c1 = marks["c0"], marks["c1"]
    record["counters"].update(
        {k: c1[k] - c0[k] for k in c0 if k != "t"},
        programs_warmed=n_programs, chunk_size=engine.chunk_size,
        kv_stats=kv)
    record["spans"] = spans
    return record


def _account(cell, records: list, t_open: float, t_close: float,
             seconds: float, lateness: list) -> dict:
    """End-to-end numbers from the client's side, over ALL the requests
    of the window."""
    arrived = [r for r in records if "new_tokens" in r
               and t_open < r["arrived"] <= t_close]
    window_s = t_close - t_open
    e2e, counters = {}, {}
    if cell.kind == "serve_closed":
        sent_in = [r for r in records if r.get("sent", t_close) < t_close
                   and r.get("arrived", t_close + 1) >= t_open]
        attempted = len(sent_in)
        failed = sum(1 for r in sent_in if "error" in r
                     or "arrived" not in r)
        e2e["serve_tokens_per_s"] = sum(
            len(r["new_tokens"]) for r in arrived) / window_s
    else:
        due_in = [r for r in records if r.get("in_window")]
        attempted = len(due_in)
        norm, failed = [], 0
        for r in due_in:
            n_out = r["item"]["max_new_tokens"]
            if "new_tokens" in r:
                norm.append((r["arrived"] - r["due"]) * 1e3 / n_out)
            else:
                failed += 1
                norm.append(REPLY_TIMEOUT_S * 1e3 / n_out)
        e2e["serve_norm_latency_p95"] = traffic.percentile(norm, 95)
        counters["norm_latency_p50"] = traffic.percentile(norm, 50)
        counters["generator_lateness"] = traffic.lateness_summary(lateness)
        counters["completed_tokens_per_s"] = sum(
            len(r["new_tokens"]) for r in arrived) / window_s
    counters.update(
        requests_arrived=len(arrived),
        prompt_tokens_arrived=sum(len(r["item"]["prompt"]) for r in arrived),
        output_tokens_arrived=sum(len(r["new_tokens"]) for r in arrived),
        mean_context_arrived=(float(np.mean(
            [len(r["item"]["prompt"]) + len(r["new_tokens"]) / 2
             for r in arrived])) if arrived else 0.0))
    never_came = sum(1 for r in records if "arrived" not in r)
    return {"attempted": attempted, "failed": failed,
            "t_window_start": t_open, "window_s": window_s,
            "end_to_end": e2e, "counters": counters,
            "finished": arrived, "never_came": never_came}


def sample_for_check(record: dict, seed: int, n: int) -> list:
    """The longest request the window finished, then ``n - 1`` more drawn
    from the seed; with shared prefixes in the mix, one of each kind."""
    fin = record["finished"]
    if not fin:
        return []
    length = lambda r: len(r["item"]["prompt"]) + len(r["new_tokens"])
    longest = max(fin, key=length)
    rest = [r for r in fin if r is not longest]
    rng = traffic.rng_for(seed, 6)
    picked = [longest]
    hits = [r for r in rest if r["item"]["shared_prefix"] is not None]
    if hits and longest["item"]["shared_prefix"] is None:
        picked.append(hits[int(rng.integers(len(hits)))])
        rest = [r for r in rest if r is not picked[-1]]
    order = rng.permutation(len(rest))
    picked += [rest[int(i)] for i in order[:max(0, n - len(picked))]]
    return picked


def _gaps(cell, seed: int, record: dict, precision: str) -> tuple:
    """(gaps per sampled request, replies of the wrong length)."""
    sz = cell.sizes
    sample = sample_for_check(record, seed, cell.traffic["check_requests"])
    wrong_length = sum(
        1 for r in record["finished"]
        if len(r["new_tokens"]) != r["item"]["max_new_tokens"])
    if not sample:
        return [], wrong_length
    arch = cell.arch
    w = weights.make_weights(
        arch, sz, weights.seed_u32(seed),
        jnp.dtype(cell.config["program"]["train"]["param_dtype"]))
    pad_to = cell.traffic["prompt_tokens"]["max"] \
        + cell.traffic["output_tokens"]["max"]
    gaps = []
    for r in sample:
        args = (arch, w, r["item"]["prompt"], r["new_tokens"], sz, pad_to)
        gaps.append(reference.served_token_gaps(*args)
                    if precision == "float32" else
                    reference.control_token_gaps(*args, precision))
    return gaps, wrong_length


def check(cell, seed: int, record: dict) -> dict:
    """One reference pass over each sampled request's prompt and served
    tokens, once the window has closed and the server is gone."""
    gaps, wrong = _gaps(cell, seed, record, "float32")
    values, notes = checks.serve_values(gaps, record["never_came"], wrong)
    # The two counts are exact comparisons: their limit is 0.
    limits = {"requests_unanswered": 0.0, "replies_wrong_length": 0.0,
              **cell.config["limits"]["serve"]}
    return checks.with_limits(values, limits, notes)


def control_readings(cell, seed: int, record: dict) -> dict:
    """The program's reading and the control's: at each position of the
    same prompts and served tokens, the token that the next precision
    down puts first."""
    low = checks.CONTROL_PRECISION[cell.config["program"]["train"]["dtype"]]
    out = {}
    for name, precision in (("program", "float32"), ("control_" + low, low)):
        gaps, wrong = _gaps(cell, seed, record, precision)
        out[name] = checks.serve_values(gaps, record["never_came"], wrong)[0]
    return out
