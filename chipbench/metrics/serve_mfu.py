"""Model-FLOPs utilisation of the serving window: the forward FLOPs of
every prompt and output token of the replies that arrived in the window
(the cell's architecture counts them, ``arch/<name>.py``
``forward_flops_per_token``: each token over its own mean context) over the
window times the chip's published peak. The whole path's share."""

from chipbench import flops


def read(run, entry):
    c = run["record"]["counters"]
    if not c.get("requests_arrived"):
        return None
    cell = run["cell"]
    ctx = c["mean_context_arrived"]
    tokens = c["prompt_tokens_arrived"] + c["output_tokens_arrived"]
    work = tokens * cell.arch.forward_flops_per_token(cell.sizes, ctx / 2)
    peak = flops.peaks(run["device"]["kind"])
    return 100.0 * work / (run["record"]["window_s"] * peak["flops_per_s"]
                           * run["device"]["count"])
