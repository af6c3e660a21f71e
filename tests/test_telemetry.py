"""Unified cluster telemetry (`serverless_learn_tpu/telemetry/`).

Fast tier: registry types (histogram bucketing, thread-safety under
concurrent increments), Prometheus text round trip over a live HTTP
endpoint, span/event-log/bench-row plumbing, `slt top` parse+render.

Slow tier (compile-heavy): the serving integration — a GenerationServer
scraped over its live /metrics endpoint (nonzero requests_total, TTFT and
queue-wait histograms), the continuous engine's cancellation path, warm's
deterministic admit buckets, and a `top --once` snapshot covering one
trainer and one inference server.
"""

import json
import os
import threading
import time

import pytest

from serverless_learn_tpu.telemetry import (JsonlEventLog, MetricsExporter,
                                            MetricsRegistry, Span,
                                            fetch_text, publish_rpc_stats)
from serverless_learn_tpu.telemetry.registry import percentile_from_buckets
from serverless_learn_tpu.telemetry.top import parse_prometheus_text, render


# -- registry types (fast) ---------------------------------------------------

def test_counter_and_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("slt_x_total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)  # counters are monotonic
    g = reg.gauge("slt_y")
    g.set(2.5)
    g.inc()
    g.dec(0.5)
    assert g.value == 3.0
    # Same (name, labels) returns the same instrument; same name with a
    # different type is a registration bug, loudly.
    assert reg.counter("slt_x_total") is c
    with pytest.raises(ValueError):
        reg.gauge("slt_x_total")


def test_histogram_bucketing_and_percentiles():
    reg = MetricsRegistry()
    h = reg.histogram("slt_lat_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.01, 0.05, 0.5, 2.0):  # edge 0.01 lands in le=0.01
        h.observe(v)
    snap = h.snapshot()
    assert snap["cumulative"] == [2, 3, 4, 5]  # le=.01, .1, 1, +Inf
    assert snap["count"] == 5
    assert abs(snap["sum"] - 2.565) < 1e-9
    p50 = h.percentile(0.5)
    assert 0.01 < p50 <= 0.1, p50  # interpolated inside the (.01, .1] bucket
    assert h.percentile(1.0) == 1.0  # +Inf bucket clamps to top edge
    assert MetricsRegistry().histogram("e").percentile(0.5) is None
    with pytest.raises(ValueError):
        reg.histogram("slt_lat_seconds", buckets=(1, 2))  # bucket mismatch
    with pytest.raises(ValueError):
        MetricsRegistry().histogram("bad", buckets=(3, 1, 2))  # unsorted


def test_concurrent_increments_are_exact():
    reg = MetricsRegistry()
    c = reg.counter("slt_n_total", engine="continuous")
    h = reg.histogram("slt_t_seconds")

    def work():
        for _ in range(5000):
            c.inc()
            h.observe(0.003)

    threads = [threading.Thread(target=work) for _ in range(8)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    assert c.value == 40000
    assert h.count == 40000
    assert abs(h.sum - 120.0) < 1e-6


def test_prometheus_text_round_trips_through_top_parser():
    reg = MetricsRegistry()
    reg.counter("slt_requests_total", engine="continuous").inc(7)
    reg.counter("slt_requests_total", engine="static").inc(2)
    reg.gauge("slt_train_loss").set(1.25)
    h = reg.histogram("slt_request_ttft_seconds", engine="continuous")
    h.observe(0.004)
    h.observe(0.02)
    text = reg.render_prometheus()
    parsed = parse_prometheus_text(text)
    # Labelled series sum per name (top shows per-endpoint rollups).
    assert parsed["values"]["slt_requests_total"] == 9
    assert parsed["values"]["slt_train_loss"] == 1.25
    ph = parsed["hists"]["slt_request_ttft_seconds"]
    assert ph["count"] == 2
    assert abs(ph["sum"] - 0.024) < 1e-9
    assert ph["cumulative"][-1] == 2
    # Percentile machinery agrees between live histogram and parsed text.
    assert abs(percentile_from_buckets(ph["buckets"], ph["cumulative"], 0.5)
               - h.percentile(0.5)) < 1e-9


def test_metrics_endpoint_http_round_trip():
    reg = MetricsRegistry()
    reg.counter("slt_requests_total").inc(3)
    reg.histogram("slt_request_queue_wait_seconds").observe(0.007)
    exp = MetricsExporter(reg).start()
    try:
        text = fetch_text(exp.addr)
        assert text == reg.render_prometheus()
        parsed = parse_prometheus_text(text)
        assert parsed["values"]["slt_requests_total"] == 3
        assert parsed["hists"]["slt_request_queue_wait_seconds"]["count"] == 1
        snap = json.loads(fetch_text(exp.addr, "/metrics.json"))
        assert snap["slt_requests_total"]["series"][0]["value"] == 3
        assert json.loads(fetch_text(exp.addr, "/healthz"))["ok"] is True
        with pytest.raises(Exception):
            fetch_text(exp.addr, "/nope")
    finally:
        exp.stop()


def test_span_marks_and_event_log(tmp_path):
    s = Span("request")
    s.mark("admit")
    time.sleep(0.002)
    s.mark("done")
    s.mark("admit")  # duplicate mark: first wins
    assert s.between(None, "admit") <= s.between(None, "done")
    assert s.between("admit", "done") >= 0.002
    assert s.between(None, "missing") is None
    log = JsonlEventLog(str(tmp_path / "events.jsonl"))
    log.emit(s.to_event())
    log.emit({"event": "other"})
    lines = [json.loads(l) for l in
             open(tmp_path / "events.jsonl").read().splitlines()]
    assert lines[0]["event"] == "span"
    assert "admit" in lines[0]["marks_s"] and "ts" in lines[0]
    assert lines[1]["event"] == "other"


def test_bench_rows_attach_percentiles():
    reg = MetricsRegistry()
    reg.counter("slt_requests_total", engine="continuous").inc(4)
    h = reg.histogram("slt_request_latency_seconds")
    for v in (0.01, 0.02, 0.04, 0.4):
        h.observe(v)
    rows = reg.bench_rows()
    by_metric = {r["metric"]: r for r in rows}
    lat = by_metric["slt_request_latency_seconds"]
    # bench.py-compatible shape: metric/value/unit, percentile fields ride
    # along so BENCH_*.json rows can adopt them without schema churn.
    assert set(lat) >= {"metric", "value", "unit", "count", "p50", "p95"}
    assert lat["count"] == 4 and lat["p50"] <= lat["p95"]
    assert by_metric["slt_requests_total_continuous"]["value"] == 4


def test_publish_rpc_stats_lands_in_registry():
    reg = MetricsRegistry()
    publish_rpc_stats(
        {"rpc/fetch": {"count": 5, "total_s": 0.5, "max_s": 0.2},
         "rpc/put": {"count": 1, "total_s": 0.1, "max_s": 0.1}},
        reg, daemon="shard-server")
    text = reg.render_prometheus()
    assert 'slt_rpc_calls{daemon="shard-server",rpc="fetch"} 5' in text
    # Re-scrape overwrites (gauge semantics): a daemon restart must not
    # double-count.
    publish_rpc_stats({"rpc/fetch": {"count": 2, "total_s": 0.1,
                                     "max_s": 0.1}}, reg,
                      daemon="shard-server")
    assert 'slt_rpc_calls{daemon="shard-server",rpc="fetch"} 2' in \
        reg.render_prometheus()


def test_rpc_stats_bounds_unknown_and_overflow_tags():
    """Regression (PR 2 satellite): a StatsReply carrying MsgType tags the
    scraper doesn't know — gaps inside the table (9..19), the daemons'
    kMaxMsgType overflow slot (32), or tags past it from a daemon built
    with a larger table — must keep their count AND max latency instead of
    being dropped or colliding."""
    from serverless_learn_tpu.utils.tracing import (K_MAX_MSG_TYPE,
                                                    MSG_TYPE_NAMES,
                                                    rpc_stats)

    class _Stat:
        def __init__(self, t, c, tot, mx):
            self.msg_type, self.count = t, c
            self.total_us, self.max_us = tot, mx

    class _Reply:
        rpc = [_Stat(3, 5, 1000, 800),           # known: heartbeat
               _Stat(13, 2, 300, 200),           # sibling-range gap
               _Stat(K_MAX_MSG_TYPE, 4, 900, 700),  # daemon overflow slot
               _Stat(40, 1, 50, 50)]             # future daemon's tag

    out = rpc_stats(_Reply())
    assert set(out) == {"rpc/heartbeat", "rpc/msg_13", "rpc/other",
                        "rpc/msg_40"}
    assert out["rpc/other"]["max_s"] == pytest.approx(700e-6)
    assert out["rpc/msg_40"]["max_s"] == pytest.approx(50e-6)
    assert MSG_TYPE_NAMES[K_MAX_MSG_TYPE] == "other"

    # publish_rpc_stats lands every series (max included) in the registry.
    reg = MetricsRegistry()
    publish_rpc_stats(out, reg, daemon="coordinator")
    text = reg.render_prometheus()
    for rpc in ("heartbeat", "msg_13", "other", "msg_40"):
        assert f'slt_rpc_calls{{daemon="coordinator",rpc="{rpc}"}}' in text
    assert 'slt_rpc_max_seconds{daemon="coordinator",rpc="other"}' in text


def test_publish_rpc_stats_clamps_malformed_entries():
    """Bounds handling: non-dict rows are skipped; NaN/inf/negative values
    clamp to 0 rather than poisoning the gauges."""
    reg = MetricsRegistry()
    publish_rpc_stats(
        {"rpc/fetch": {"count": -3, "total_s": float("nan"),
                       "max_s": float("inf")},
         "rpc/garbage": "not-a-dict",
         "rpc/put": {"count": 2, "total_s": 0.5, "max_s": 0.4}},
        reg, daemon="shard-server")
    text = reg.render_prometheus()
    assert 'slt_rpc_calls{daemon="shard-server",rpc="fetch"} 0' in text
    assert 'slt_rpc_time_seconds{daemon="shard-server",rpc="fetch"} 0' in text
    assert 'slt_rpc_max_seconds{daemon="shard-server",rpc="fetch"} 0' in text
    assert "garbage" not in text
    assert 'slt_rpc_max_seconds{daemon="shard-server",rpc="put"} 0.4' in text


def test_debug_profile_endpoint(tmp_path):
    """Satellite: /debug/profile captures an on-demand jax.profiler trace
    from a live metrics server; disabled (404) without --profile-dir;
    bad/oversized seconds are 400."""
    import urllib.error

    disabled = MetricsExporter(MetricsRegistry()).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            fetch_text(disabled.addr, "/debug/profile?seconds=1")
        assert ei.value.code == 404
    finally:
        disabled.stop()

    exp = MetricsExporter(MetricsRegistry(),
                          profile_dir=str(tmp_path / "prof")).start()
    try:
        for q, code in (("seconds=abc", 400), ("seconds=9999", 400)):
            with pytest.raises(urllib.error.HTTPError) as ei:
                fetch_text(exp.addr, f"/debug/profile?{q}")
            assert ei.value.code == code
        rep = json.loads(fetch_text(exp.addr, "/debug/profile?seconds=0.2",
                                    timeout=60))
        assert rep["ok"] is True
        assert os.path.isdir(rep["dir"])
        # The capture produced profiler artifacts, not an empty dir.
        found = []
        for root, _, files in os.walk(rep["dir"]):
            found += files
        assert found, "profile capture wrote no files"
    finally:
        exp.stop()


def test_top_renders_trainer_and_inference_sections():
    """Pure-python `slt top` smoke: two endpoints, one publishing trainer
    metrics, one inference metrics, rendered into one screen."""
    infer = MetricsRegistry()
    infer.counter("slt_requests_total", engine="continuous").inc(12)
    infer.histogram("slt_request_ttft_seconds",
                    engine="continuous").observe(0.004)
    infer.gauge("slt_slots_in_use", engine="continuous").set(3)
    train = MetricsRegistry()
    train.counter("slt_train_steps_total").inc(20)
    train.gauge("slt_train_samples_per_sec").set(1234.5)
    train.gauge("slt_train_loss").set(2.31)
    e1, e2 = MetricsExporter(infer).start(), MetricsExporter(train).start()
    try:
        from serverless_learn_tpu.telemetry.top import EndpointState

        states = [EndpointState(e1.addr), EndpointState(e2.addr)]
        for st in states:
            st.poll()
        screen = render(states)
        assert "INFERENCE" in screen and "TRAINING" in screen
        assert e1.addr in screen and e2.addr in screen
        assert "12" in screen and "2.3100" in screen
        # A dead endpoint renders as DOWN, not a crash.
        dead = EndpointState("127.0.0.1:1")
        dead.poll()
        assert "DOWN" in render([dead])
    finally:
        e1.stop()
        e2.stop()


def test_diloco_nonleader_liveness_escape(tmp_path):
    """ADVICE round 5: a leader whose heartbeat thread outlives a wedged
    training thread keeps its lease forever; non-leaders must not poll
    unboundedly. After liveness_factor * round_timeout_s with no new
    anchor and LATEST unmoved, a non-leader challenges — leads the round
    itself — and the escape is counted."""
    import numpy as np

    from serverless_learn_tpu.training import diloco_dcn as dd
    from serverless_learn_tpu.training.checkpoint import LocalStore

    isl = dd.DilocoIsland.__new__(dd.DilocoIsland)
    isl.store = LocalStore(str(tmp_path))
    isl.run = "t"
    isl.poll_s = 0.01
    isl.round_timeout_s = 0.05
    isl.liveness_factor = 2.0
    isl.outer_lr, isl.outer_momentum = 0.1, 0.9
    isl.report = dd.IslandReport()
    isl.abort = None
    reg = MetricsRegistry()
    isl._m_rounds = reg.counter("slt_diloco_rounds_total")
    isl._m_led = reg.counter("slt_diloco_led_rounds_total")
    isl._m_escapes = reg.counter("slt_diloco_liveness_escapes_total")
    isl._m_round = reg.gauge("slt_diloco_round")
    isl._m_lag = reg.gauge("slt_diloco_anchor_lag_rounds")

    class FakeAgent:
        worker_id = 7

    isl.agent = FakeAgent()
    # id 3 is the hung leader: live in membership, never publishes.
    isl._live_ids = lambda: [3, 7]
    template = {"w": np.zeros((2,), np.float32)}
    anchor = {"w": np.ones((2,), np.float32)}
    trace = {"w": np.zeros((2,), np.float32)}
    isl._publish(0, anchor, trace, 0)
    t0 = time.time()
    isl._await_next_anchor(0, anchor, trace, template)
    assert time.time() - t0 < 10, "non-leader waited unboundedly"
    assert isl.store.exists(isl._k("round-1", "anchor")), \
        "challenger did not publish the next anchor"
    assert isl._m_escapes.value == 1
    assert isl.report.led_rounds == 1


# -- serving integration (compile-heavy; slow tier) --------------------------

@pytest.fixture(scope="module")
def model(devices):
    import jax
    import jax.numpy as jnp

    from serverless_learn_tpu.models.registry import get_model

    bundle = get_model("llama_tiny", dtype=jnp.float32,
                       param_dtype=jnp.float32, max_seq_len=64)
    params = bundle.module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return bundle.module, params


def test_server_metrics_endpoint_scrape(model):
    """Acceptance: a live /metrics endpoint on the serving process from
    which a scrape reads nonzero requests_total plus TTFT and queue-wait
    histograms recorded per request."""
    from serverless_learn_tpu.inference.server import (GenerationServer,
                                                       request)

    module, params = model
    reg = MetricsRegistry()
    srv = GenerationServer(module, params, engine="continuous",
                           registry=reg, metrics_port=0).start()
    try:
        assert srv.metrics_addr
        prompts = [[5, 9, 11], [7, 3, 2, 8], [4, 4], [1, 2, 3]]
        reps = [None] * len(prompts)

        def client(i):
            reps[i] = request(srv.addr, {"prompt": prompts[i],
                                         "max_new_tokens": 4})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        [t.start() for t in threads]
        [t.join(timeout=300) for t in threads]
        assert all(r and "new_tokens" in r for r in reps), reps
        parsed = parse_prometheus_text(fetch_text(srv.metrics_addr))
        assert parsed["values"]["slt_requests_total"] >= 4
        assert parsed["values"]["slt_server_requests_total"] >= 4
        ttft = parsed["hists"]["slt_request_ttft_seconds"]
        qwait = parsed["hists"]["slt_request_queue_wait_seconds"]
        assert ttft["count"] >= 4 and ttft["sum"] > 0
        assert qwait["count"] >= 4
        assert parsed["values"]["slt_decode_tokens_total"] >= 16
        # Span-derived ordering: queueing is part of TTFT, so per-request
        # TTFT can never be cheaper than its queue wait in aggregate.
        assert ttft["sum"] >= qwait["sum"]
    finally:
        srv.stop()


def test_continuous_cancellation_retires_slot(model):
    """ADVICE round 5: a submit() that times out must not decode to full
    budget — the request retires at the next boundary and the counter
    records it."""
    from serverless_learn_tpu.inference.continuous import (
        ContinuousBatchingEngine)

    module, params = model
    reg = MetricsRegistry()
    eng = ContinuousBatchingEngine(module, params, max_slots=2,
                                   chunk_size=2, registry=reg)
    try:
        # timeout 0: guaranteed to abandon (queued or just-admitted).
        r = eng.submit([5, 6], 40, 0.0, 0, None, 0, timeout_s=0.0)
        assert "error" in r and "timed out" in r["error"], r
        deadline = time.time() + 60
        while eng.requests_cancelled < 1 and time.time() < deadline:
            time.sleep(0.02)
        assert eng.requests_cancelled == 1
        deadline = time.time() + 60
        while (any(s is not None for s in eng._slots)
               and time.time() < deadline):
            time.sleep(0.02)
        assert all(s is None for s in eng._slots), \
            "cancelled request kept its slot"
        c = reg.counter("slt_requests_cancelled_total", engine="continuous")
        assert c.value == 1
        # Engine still serves live traffic after the retirement.
        import jax
        import jax.numpy as jnp

        from serverless_learn_tpu.inference.generate import generate

        ok = eng.submit([5, 9, 11], 4, 0.0, 0, None, 0)
        solo = [int(t) for t in jax.device_get(generate(
            module, params, jnp.asarray([[5, 9, 11]], jnp.int32), 4))[0][3:]]
        assert ok["new_tokens"] == solo
    finally:
        eng.stop()


def test_top_once_covers_trainer_and_inference(model, capsys):
    """Acceptance: `slt top --once` renders a one-shot cluster snapshot
    spanning one trainer and one inference server."""
    from serverless_learn_tpu.cli import main
    from serverless_learn_tpu.config import (DataConfig, ExperimentConfig,
                                             MeshConfig, OptimizerConfig,
                                             TrainConfig)
    from serverless_learn_tpu.inference.server import (GenerationServer,
                                                       request)
    from serverless_learn_tpu.telemetry import get_registry
    from serverless_learn_tpu.training.loop import run_training

    # Trainer arm: a short real run publishing into the process-default
    # registry, exported like `train --metrics-port 0` would.
    cfg = ExperimentConfig(
        model="mlp_mnist", mesh=MeshConfig(dp=8),
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
        train=TrainConfig(batch_size=16, num_steps=3, dtype="float32",
                          param_dtype="float32"),
        data=DataConfig())
    run_training(cfg)
    train_exp = MetricsExporter(get_registry()).start()

    # Inference arm: its own registry + endpoint, like a second process.
    module, params = model
    srv = GenerationServer(module, params, engine="continuous",
                           registry=MetricsRegistry(), metrics_port=0)
    srv.start()
    try:
        assert "new_tokens" in request(
            srv.addr, {"prompt": [5, 9, 11], "max_new_tokens": 4})
        rc = main(["top", f"{train_exp.addr},{srv.metrics_addr}", "--once"])
        assert rc == 0
        screen = capsys.readouterr().out
        assert "TRAINING" in screen and "INFERENCE" in screen
        assert train_exp.addr in screen and srv.metrics_addr in screen
    finally:
        srv.stop()
        train_exp.stop()
