"""The readers that count the work that ran, on planted reduced traces
and records with hand counts: the flash kernels told apart by name (a
dk/dv call is never a forward call; an unknown kernel gives nothing),
``useful_decode_share`` from the scheduler's records, and
``decode_roofline`` over whole ``jit_chunk`` events only (an event cut by
either edge of the trace leaves it where it was)."""

import pytest

import tiny
from chipbench import run
from chipbench.cell import load_cell

V5E = {"kind": "TPU v5 lite", "count": 1}


def _read(name, run_):
    return run.load_metric_reader(name, tiny.BENCH)(run_, None)


# ---- the flash kernels, by name ------------------------------------------

def _kernel_text(name, results):
    return (f"%{name} = ({results}) custom-call(%q, %k, %v), "
            f'custom_call_target="tpu_custom_call", backend_config={{}}')


FWD = "bf16[2,32,4096,128]{3,2,1,0}, f32[2,32,4096,1]{3,2,1,0}"
DQ = "bf16[2,32,4096,128]{3,2,1,0}"
# The dk/dv kernel's two results are float32, like the forward's row
# statistics: the reader that went by result types took it for a forward.
DKV = "f32[2,8,4096,128]{3,2,1,0}, f32[2,8,4096,128]{3,2,1,0}"


def _train_run(ops):
    """``ops``: {op name: (calls, seconds each, result types)}; beside
    the kernels one fusion, which no flash reader may count."""
    ops = dict(ops, **{"fusion.7": (3, 0.05, None)})
    trace = {"ops": {}, "op_counts": {}, "op_text": {}}
    for name, (calls, each, results) in ops.items():
        trace["ops"][name] = calls * each
        trace["op_counts"][name] = float(calls)
        trace["op_text"][name] = (
            _kernel_text(name, results) if results else
            f"%{name} = bf16[8]{{0}} fusion(%x), kind=kLoop")
    return {"cell": load_cell("mistral7b-lora-train-4k"), "trace": trace,
            "device": dict(V5E), "notes": {}}


# Three steps of the training cell as the ledger's PR 26 lines have them:
# per layer and step the forward twice (remat), dq and dk/dv once; the
# instructions are numbered, several to a name.
THREE_STEPS = {
    "flash_fwd.37": (48, 6.34e-3, FWD), "flash_fwd.41": (48, 6.34e-3, FWD),
    "flash_bwd_dq.5": (48, 4.07e-3, DQ),
    "flash_bwd_dkv.6": (24, 5.38e-3, DKV),
    "flash_bwd_dkv.9": (24, 5.38e-3, DKV),
}
# By hand, 2 x 4096 tokens, 32 heads of 128: one product over the causal
# half is 2 * 32 * 128 * 2 * 4096 * 2048 FLOPs; the forward does two, the
# backward five; both are compute-bound.
PRODUCT = 2 * 32 * 128 * 2 * 4096 * 2048
T_FWD, T_BWD = 2 * PRODUCT / 197e12, 5 * PRODUCT / 197e12


def test_flash_rooflines_by_hand():
    r = _train_run(THREE_STEPS)
    whole = _read("flash_attn_roofline.train", r)
    fwd = _read("flash_fwd_roofline.train", r)
    bwd = _read("flash_bwd_roofline.train", r)
    assert fwd == pytest.approx(100 * T_FWD / 6.34e-3)          # 22.0
    assert bwd == pytest.approx(100 * T_BWD / (4.07e-3 + 5.38e-3))  # 36.9
    assert whole == pytest.approx(
        100 * (96 * T_FWD + 48 * T_BWD)
        / (96 * 6.34e-3 + 48 * (4.07e-3 + 5.38e-3)))            # 28.4
    assert (round(fwd, 1), round(bwd, 1), round(whole, 1)) == \
        (22.0, 36.9, 28.4)
    # The calls it divided are in the line's notes: 96 / 48 / 48.
    calls = {k: v["calls"] for k, v in r["notes"]["flash_kernels"].items()}
    assert calls == {"flash_fwd": 96, "flash_bwd_dq": 48,
                     "flash_bwd_dkv": 48}


def test_a_dkv_call_is_never_counted_as_forward():
    slow_dkv = dict(THREE_STEPS)
    slow_dkv["flash_bwd_dkv.6"] = (24, 50e-3, DKV)
    a, b = _train_run(THREE_STEPS), _train_run(slow_dkv)
    assert _read("flash_fwd_roofline.train", a) == \
        _read("flash_fwd_roofline.train", b)
    assert _read("flash_bwd_roofline.train", b) < \
        _read("flash_bwd_roofline.train", a)
    # The parent's reader, by result types, on the same events: 144
    # "forward" calls and 24 "backward" ones, the 26.80 the ledger holds.
    by_types = 100 * (144 * T_FWD + 24 * T_BWD) / (
        96 * 6.34e-3 + 48 * (4.07e-3 + 5.38e-3))
    assert by_types == pytest.approx(26.8, abs=0.15)
    assert _read("flash_attn_roofline.train", a) > by_types + 1.0


@pytest.mark.parametrize("stem", ["flash_attn_roofline", "flash_fwd_roofline",
                                  "flash_bwd_roofline"])
def test_an_unknown_kernel_name_gives_nothing_and_says_so(stem):
    r = _train_run(dict(THREE_STEPS, **{"attn.12": (48, 5e-3, DQ)}))
    assert _read(stem + ".train", r) is None
    assert r["notes"]["flash_kernels_unknown"] == ["attn"]


def test_unpaired_backward_events_give_nothing():
    ops = dict(THREE_STEPS)
    del ops["flash_bwd_dkv.9"]
    r = _train_run(ops)
    assert _read("flash_bwd_roofline.train", r) is None
    assert r["notes"]["flash_kernels_unpaired"] == [48, 24]


@pytest.mark.parametrize("stem", ["flash_attn_roofline", "flash_fwd_roofline",
                                  "flash_bwd_roofline"])
def test_no_kernel_in_the_trace_gives_nothing(stem):
    assert _read(stem + ".train", _train_run({})) is None


def test_the_recorded_trace_has_no_flash_kernel():
    from chipbench import trace_reduce

    r = _train_run({})
    r["trace"] = trace_reduce.reduce_trace(tiny.FIXTURE_TRACE)
    assert _read("flash_attn_roofline.train", r) is None


# ---- useful_decode_share --------------------------------------------------

def _iter(**fields):
    return dict({"event": "sched_iter", "max_slots": 8, "decode_steps": 32,
                 "tokens_out": 0, "requests_finished": 0}, **fields)


def test_useful_decode_share_by_hand():
    spans = [
        _iter(tokens_out=200, requests_finished=1),
        _iter(tokens_out=150, requests_finished=2),
        # Prefill only: no chunk, its first tokens came out of prefill.
        _iter(decode_steps=0, tokens_out=3),
        _iter(decode_steps=64, tokens_out=256),
        {"event": "span", "span": "request"},
    ]
    got = _read("useful_decode_share.tput", {"record": {"spans": spans}})
    assert got == pytest.approx(
        100.0 * (200 + 150 + 3 + 256 - 3) / ((32 + 32 + 64) * 8))
    # PR 27's window by hand: 104 chunks, 17,631 tokens, 152 replies.
    window = [_iter(tokens_out=17631, requests_finished=152,
                    decode_steps=104 * 32)]
    assert _read("useful_decode_share.tput",
                 {"record": {"spans": window}}) == pytest.approx(65.65,
                                                                 abs=0.01)


def test_useful_decode_share_cannot_pass_100():
    """Replies of 33 tokens in full chunks: 32 tokens each from one chunk,
    the first from prefill."""
    spans = [_iter(tokens_out=8 * 33, requests_finished=8)]
    assert _read("useful_decode_share.tput",
                 {"record": {"spans": spans}}) == pytest.approx(100.0)


def test_useful_decode_share_is_silent_without_a_chunk():
    for spans in ([], [_iter(decode_steps=0, tokens_out=5)]):
        assert _read("useful_decode_share.tput",
                     {"record": {"spans": spans}}) is None
    assert _read("useful_decode_share.tput", {"record": {}}) is None


# ---- decode_roofline over whole chunks only -------------------------------

CHUNK_S = 0.376
COUNTERS = {"chunks_run": 100, "decoded_rows": 760, "chunk_size": 32,
            "mean_context_arrived": 480.0}


def _serve_run(events, recorded):
    return {"cell": load_cell("mistral7b-serve-backlog"),
            "trace": {"module_events": events, "recorded": recorded},
            "record": {"counters": dict(COUNTERS), "spans": []},
            "device": dict(V5E), "notes": {}}


def _by_hand():
    """7.6 rows a step at 480 tokens of context, 16 layers of
    Mistral-7B-v0.3 and its head in bf16, 819 GB/s: memory-bound."""
    layer = 4096 * 32 * 128 * 2 + 2 * 4096 * 8 * 128 + 3 * 4096 * 14336
    nbytes = 2 * (16 * layer + 4096 * 32768) \
        + 7.6 * 480.0 * (2 * 16 * 8 * 128 * 2)
    return 100.0 * 32 * (nbytes / 819e9) / CHUNK_S


# Three whole chunks with prefill programs between them, in a trace that
# neither begins nor ends inside a chunk.
WHOLE = [(0.00, 0.01, "jit_pre"),
         (0.01, 0.01 + CHUNK_S, "jit_chunk"), (0.40, 0.41, "jit_pre"),
         (0.41, 0.41 + CHUNK_S, "jit_chunk"), (0.80, 0.81, "jit_pre"),
         (0.81, 0.81 + CHUNK_S, "jit_chunk"), (1.19, 1.20, "jit_pre")]


def test_decode_roofline_by_hand():
    r = _serve_run(WHOLE, (0.0, 1.20))
    got = _read("decode_roofline.tput", r)
    assert got == pytest.approx(_by_hand())
    assert 76.0 < got < 80.0
    assert r["notes"]["decode_chunks_whole"] == 3


@pytest.mark.parametrize("cut", [
    [(1.20, 1.294, "jit_chunk")],                    # the trace stopped in it
    [(-0.2, 0.0, "jit_chunk")],                      # it began in one
    [(-0.2, 0.0, "jit_chunk"), (1.20, 1.21, "jit_chunk")],
], ids=["at_the_stop", "at_the_start", "at_both"])
def test_a_cut_chunk_leaves_decode_roofline_where_it_was(cut):
    events = sorted(WHOLE + cut)
    recorded = (events[0][0], max(e for _, e, _ in events))
    assert _read("decode_roofline.tput", _serve_run(events, recorded)) == \
        pytest.approx(_by_hand())
    # The parent's reader on the same events: every event a whole chunk.
    spans = [e - s for s, e, n in events if "chunk" in n]
    parent = _by_hand() * len(spans) * CHUNK_S / sum(spans)
    assert parent > _by_hand() + 10.0


def test_decode_roofline_is_silent_without_a_whole_chunk():
    cut_only = [(0.0, 0.3, "jit_chunk"), (0.3, 0.31, "jit_pre"),
                (0.31, 0.5, "jit_chunk")]
    assert _read("decode_roofline.tput",
                 _serve_run(cut_only, (0.0, 0.5))) is None
    r = _serve_run(WHOLE, (0.0, 1.20))
    r["record"]["counters"]["chunks_run"] = 0
    assert _read("decode_roofline.tput", r) is None


def test_decode_roofline_hands_the_runs_record_to_the_architecture(
        monkeypatch):
    r = _serve_run(WHOLE, (0.0, 1.20))
    arch, seen = r["cell"].arch, {}
    real = arch.decode_step_cost

    def spy(sz, rows, mean_context, record):
        seen.update(rows=rows, record=record)
        return real(sz, rows, mean_context, record)

    monkeypatch.setattr(arch, "decode_step_cost", spy)
    _read("decode_roofline.tput", r)
    assert seen["rows"] == pytest.approx(7.6)
    assert seen["record"] is r["record"]
