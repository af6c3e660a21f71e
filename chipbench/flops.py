"""Operations and bytes from shapes, and the table of peaks.

Nothing here reads the program or a compiled cost analysis: XLA's own
count includes recomputation (an HFU), and the yardstick must not move
when the program is refactored. Counts are multiply-adds times two.
"""

from __future__ import annotations

from chipbench.weights import Sizes

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


def layer_matmul_params(sz: Sizes) -> int:
    """Weights of one block that a token multiplies (norm scales apart)."""
    d, H, K, D, F = sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim, sz.d_ff
    return d * H * D + 2 * d * K * D + H * D * d + 3 * d * F


def layer_adapter_params(sz: Sizes) -> int:
    d, H, K, D, r = (sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim,
                     sz.lora_rank)
    return (d * r + r * H * D) + (d * r + r * K * D) if r else 0


def head_params(sz: Sizes) -> int:
    return sz.d_model * sz.vocab


def weight_bytes(sz: Sizes, bytes_per_param: int = 2) -> int:
    """Bytes a decode step has to stream: every block and the head (the
    embedding contributes one row per token)."""
    return bytes_per_param * (sz.n_layers * layer_matmul_params(sz)
                              + head_params(sz))


def attention_flops(sz: Sizes, n_query: int, n_keys: float) -> float:
    """QK^T and PV of one layer: ``n_query`` queries, each over ``n_keys``
    keys (the mean number it may see, T/2 under a causal mask)."""
    return 2 * 2 * sz.n_heads * sz.head_dim * n_query * n_keys


def forward_flops_per_token(sz: Sizes, mean_keys: float) -> float:
    """One token through every block and the head, seeing ``mean_keys``
    keys in each attention layer."""
    mm = sz.n_layers * (layer_matmul_params(sz) + layer_adapter_params(sz))
    return (2 * (mm + head_params(sz))
            + sz.n_layers * attention_flops(sz, 1, mean_keys))


def lora_train_flops_per_token(sz: Sizes, seq_len: int) -> float:
    """What one LoRA step *requires* per token: the forward pass, the
    backward pass for activations through every frozen product (as much
    again), attention's backward (four products for the forward's two),
    and both gradients of the adapters. No gradient of a frozen weight and
    no recomputation: a step that recomputes does more than this, and its
    utilisation by this count is the lower for it."""
    frozen = 2 * (sz.n_layers * layer_matmul_params(sz) + head_params(sz))
    adapters = 2 * sz.n_layers * layer_adapter_params(sz)
    attn = sz.n_layers * attention_flops(sz, 1, seq_len / 2)
    return 2 * frozen + 3 * adapters + 3 * attn


def flash_attention_cost(sz: Sizes, batch: int, seq_len: int,
                         bytes_per_el: int = 2) -> dict:
    """Causal flash attention over ``batch`` sequences, ONE layer, forward
    and backward together. FLOPs: the forward's two products over the
    causal half, the backward's five (it recomputes the scores) over the
    same. Bytes: the least traffic, each operand once: forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv."""
    H, K, D = sz.n_heads, sz.n_kv_heads, sz.head_dim
    causal = batch * seq_len * (seq_len / 2)
    per_product = 2 * H * D * causal
    q_el = batch * seq_len * H * D
    kv_el = batch * seq_len * K * D
    fwd_bytes = bytes_per_el * (2 * q_el + 2 * kv_el)
    bwd_bytes = bytes_per_el * (4 * q_el + 4 * kv_el)
    return {"fwd_flops": 2 * per_product, "bwd_flops": 5 * per_product,
            "fwd_bytes": fwd_bytes, "bwd_bytes": bwd_bytes}


def kv_bytes_per_token(sz: Sizes, bytes_per_el: int = 2) -> int:
    return 2 * sz.n_layers * sz.n_kv_heads * sz.head_dim * bytes_per_el


def decode_step_cost(sz: Sizes, rows: float, mean_context: float) -> dict:
    """One decode step over ``rows`` live sequences: every weight once,
    each row's keys and values once."""
    flops = rows * forward_flops_per_token(sz, mean_context)
    nbytes = weight_bytes(sz) + rows * mean_context * kv_bytes_per_token(sz)
    return {"flops": flops, "bytes": nbytes}


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least time the chip could take, which bound binds)."""
    t_f, t_b = flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
