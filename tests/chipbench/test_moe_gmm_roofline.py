"""``metrics/moe_gmm_roofline.py`` on planted reduced traces of the
Trinity cell's programs, with hand counts: a decode chunk whose calls
took their least time reads 100; decode calls count the experts the
engine says its steps read; a launch that the trace's edge cuts counts
at the share of it the trace holds; small prefill programs are left out
and counted in the notes; no ``moe_gmm`` event (the parent's program,
every other cell, the recorded fixture trace) gives nothing."""

import pytest

import tiny
from chipbench import run, trace_reduce
from chipbench.cell import load_cell

V5E = {"kind": "TPU v5 lite", "count": 1}
CELL = "trinity-mini-serve-long-in"
# Trinity-Mini's expert layers: 4 of them, 128 experts of hidden 2,048
# and width 1,024, top 8; the engine's 32-step decode chunk over 32 slots.
LAYERS, E, D, F, TOP, STEPS = 4, 128, 2048, 1024, 8, 32
HBM, PEAK = 819e9, 197e12


def _least_layer(rows, experts):
    """By hand: gate and up (k D, n F) and down (k F, n D), each the
    larger of its FLOPs at peak and its bytes (experts' weights, rows in
    and out, bf16) at the chip's bandwidth."""
    m = rows * TOP
    gate = max(2 * m * D * F / PEAK, 2 * (experts * D * F + m * D + m * F)
               / HBM)
    down = max(2 * m * F * D / PEAK, 2 * (experts * F * D + m * F + m * D)
               / HBM)
    return 2 * gate + down


def _kernel_text(name):
    return (f"%{name} = bf16[256,1024]{{1,0}} custom-call(%a, %b), "
            f'custom_call_target="tpu_custom_call", backend_config={{}}')


def _run(programs, kernel_s, experts_read=110.0, kernel="moe_gmm"):
    """``programs``: [(start, end, name)] on device 0; the kernel's
    events: 12 instructions sharing ``kernel_s`` seconds; beside them a
    fusion, which the reader must not count. The records: one
    iteration of 32 decode steps whose steps read ``experts_read``
    experts per expert layer."""
    ops = {f"{kernel}.{i}": kernel_s / 12 for i in range(12)}
    ops["fusion.3"] = 0.5
    trace = {
        "ops": ops, "op_counts": {n: 1.0 for n in ops},
        "op_text": {n: _kernel_text(n) if n.startswith(kernel)
                    else f"%{n} = bf16[8]{{0}} fusion(%x), kind=kLoop"
                    for n in ops},
        "module_events": list(programs),
        "recorded": (0.0, 100.0)}
    record = {"counters": {"chunk_size": STEPS},
              "spans": [{"event": "sched_iter", "decode_steps": STEPS,
                         "experts_read": STEPS * LAYERS * experts_read}]}
    return {"cell": load_cell(CELL), "trace": trace, "record": record,
            "device": dict(V5E), "notes": {}}


def _read(run_):
    return run.load_metric_reader("moe_gmm_roofline.tput", tiny.BENCH)(
        run_, None)


CHUNK = (1.0, 2.0, "jit_chunk_n32w256")
PRE = (3.0, 3.05, "jit_pre_n32t32w256")


def test_a_decode_chunk_at_its_least_time_reads_100():
    least = STEPS * LAYERS * _least_layer(32, 110.0)
    r = _run([CHUNK], least)
    assert _read(r) == pytest.approx(100.0)
    notes = r["notes"]["moe_gmm"]
    assert notes["calls_counted"] == STEPS * LAYERS * 3
    assert notes["bound"] == {"chunk": "memory"}
    # Twice as long on the device: half the share.
    assert _read(_run([CHUNK], 2 * least)) == pytest.approx(50.0)


def test_decode_calls_count_the_experts_the_steps_read():
    few, many = (STEPS * LAYERS * _least_layer(32, e) for e in (40.0, 110.0))
    r_few = _run([CHUNK], 1.0, experts_read=40.0)
    r_many = _run([CHUNK], 1.0, experts_read=110.0)
    assert _read(r_few) == pytest.approx(100.0 * few)
    assert _read(r_many) == pytest.approx(100.0 * many)
    assert many / few > 2.5      # memory-bound: the experts' weights


def test_prefill_reads_every_held_expert_and_small_programs_are_noted():
    least = LAYERS * _least_layer(32 * 32, E)
    r = _run([PRE, (4.0, 4.01, "jit_pre_n2t32w64")], least)
    assert _read(r) == pytest.approx(100.0)
    notes = r["notes"]["moe_gmm"]
    assert notes["small_prefill_left_out"] == 1
    assert notes["calls_counted"] == LAYERS * 3
    assert notes["bound"] == {"pre": "memory"}
    # nb 4 of 32 tokens is 128 tokens: counted, not left out.
    r4 = _run([PRE, (4.0, 4.01, "jit_pre_n4t32w64")], 1.0)
    _read(r4)
    assert r4["notes"]["moe_gmm"]["small_prefill_left_out"] == 0


def test_a_launch_cut_by_the_traces_edge_counts_at_its_share():
    """Two whole chunks of 1 s and one the trace holds the last 0.25 s
    of: 2.25 chunks' calls. A cut launch of a program the trace holds no
    whole launch of is counted in the notes and not in the least time."""
    chunks = [(0.0, 0.25, "jit_chunk_n32w256"), CHUNK,
              (3.0, 4.0, "jit_chunk_n32w256"),
              (99.99, 100.0, "jit_pre_n16t32w256")]
    least = 2.25 * STEPS * LAYERS * _least_layer(32, 110.0)
    r = _run(chunks, least)
    assert _read(r) == pytest.approx(100.0)
    assert r["notes"]["moe_gmm"]["calls_counted"] == pytest.approx(
        2.25 * STEPS * LAYERS * 3)
    assert r["notes"]["moe_gmm"]["cut_unmeasured"] == 1


@pytest.mark.parametrize("kernel", ["ragged-dot-none", "flash_fwd"])
def test_no_moe_gmm_event_gives_nothing(kernel):
    """The parent's program: its grouped products are the compiler's
    ``ragged-dot-none`` kernels."""
    r = _run([CHUNK, PRE], 1.0, kernel=kernel)
    assert _read(r) is None
    assert "moe_gmm" not in r["notes"]


def test_a_cell_without_experts_gives_nothing():
    r = _run([CHUNK], 1.0)
    r["cell"] = load_cell("mistral7b-serve-backlog")
    assert _read(r) is None


def test_the_recorded_trace_gives_nothing():
    """The fixture trace (a TPU v5e, three fused matmuls a launch, no
    kernel and no serving program): nothing."""
    reduced = trace_reduce.reduce_trace(tiny.FIXTURE_TRACE)
    r = _run([], 1.0)
    r["trace"] = reduced
    assert _read(r) is None
