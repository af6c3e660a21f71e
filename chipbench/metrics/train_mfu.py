"""Model-FLOPs utilisation of the training window: the FLOPs a step
requires per token (the cell's architecture counts them, ``arch/<name>.py``
``train_flops_per_token``: no gradient of a frozen weight, no
recomputation) times the window's tokens per second, over the chip's
published peak. The whole step's share; bounds every kernel's roofline."""

from chipbench import flops


def read(run, entry):
    rate = run["end_to_end"].get("train_tokens_per_s")
    if rate is None:
        return None
    cell = run["cell"]
    per_token = cell.arch.train_flops_per_token(
        cell.sizes, cell.traffic["tokens_per_sequence"])
    peak = flops.peaks(run["device"]["kind"])
    return 100.0 * per_token * rate / (peak["flops_per_s"]
                                       * run["device"]["count"])
