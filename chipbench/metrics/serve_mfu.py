"""Model-FLOPs utilisation of the serving window: the forward FLOPs of
every prompt and output token of the replies that arrived in the window
(``chipbench/flops.py``, each token over its own mean context) over the
window times the chip's published peak. The whole path's share."""

from chipbench import flops


def read(run, entry):
    c = run["record"]["counters"]
    if not c.get("requests_arrived"):
        return None
    sz = run["cell"].sizes
    ctx = c["mean_context_arrived"]
    tokens = c["prompt_tokens_arrived"] + c["output_tokens_arrived"]
    work = tokens * flops.forward_flops_per_token(sz, ctx / 2)
    peak = flops.peaks(run["device"]["kind"])
    return 100.0 * work / (run["record"]["window_s"] * peak["flops_per_s"]
                           * run["device"]["count"])
