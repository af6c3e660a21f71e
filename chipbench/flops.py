"""The table of peaks, and the least time a count of operations and
bytes could take on a chip. The counts themselves come from shapes and
are the architecture's (``arch/<name>.py``: ``forward_flops_per_token``,
``train_flops_per_token``, ``decode_step_cost``, ``flash_attention_cost``).
"""

from __future__ import annotations

# Published peaks per chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" system architecture
# (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s). Copied from the program's
# ``utils/flops.py`` table (exact match, unknown kind raises).
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least time the chip could take, which bound binds)."""
    t_f, t_b = flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
