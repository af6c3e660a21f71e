"""The plain reference: Mistral-style decoder in float32 ``jax.numpy``.

Follows the published description (RMSNorm, grouped-query attention with
rotary positions, SwiGLU, untied head) with no kernel, no cache and no
batching tricks, every matrix product at ``highest`` precision. It imports
nothing of the program. Two departures, both to match what the program
runs (listed under ``assumed`` in the configuration files): rotary pairs
are interleaved (x[2i], x[2i+1]) where the published code rotates halves
(the same function up to a fixed permutation of each head's columns,
which seeded random weights absorb), and the norm's epsilon is the
program's 1e-6.

``matmul`` is pluggable so that the control can run the same mathematics
in the next precision down (``fp8_matmul``): ``correct`` has to reject it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench.weights import ADAPTER_LEAVES, Sizes

F32 = jnp.float32


def f32_matmul(x, w, spec: str):
    return jnp.einsum(spec, x.astype(F32), w.astype(F32),
                      precision=lax.Precision.HIGHEST)


def _fp8(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32.
    Straight-through for gradients: the forward pass is the fp8 one, the
    backward pass sees the identity (unscaled gradients would underflow
    e4m3 to nought, which no fp8 training path does)."""
    x = x.astype(F32)
    scale = lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + lax.stop_gradient(q - x)


def fp8_matmul(x, w, spec: str):
    """The control's product: both operands in fp8, accumulated in f32."""
    return jnp.einsum(spec, _fp8(x), _fp8(w),
                      precision=lax.Precision.HIGHEST)


def bf16_matmul(x, w, spec: str):
    """Both operands rounded to bfloat16, accumulated in float32."""
    r = lambda a: a.astype(jnp.bfloat16).astype(F32)
    return jnp.einsum(spec, r(x), r(w), precision=lax.Precision.HIGHEST)


MATMULS = {"float32": f32_matmul, "fp8": fp8_matmul, "bfloat16": bf16_matmul}


def rms_norm(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(ms + eps) * scale.astype(F32)


def rope(x, theta):
    """x [B, T, H, D] at positions 0..T-1; rotates pairs (x[2i], x[2i+1])."""
    T, D = x.shape[1], x.shape[3]
    freqs = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(T, dtype=F32)[:, None] * freqs
    s, c = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                     axis=-1).reshape(x.shape)


def _attention_one(q, k, v, mm):
    """q [B, T, G, D] (the G query heads of one KV head), k, v [B, T, D]."""
    T, D = q.shape[1], q.shape[3]
    s = mm(q, k, "btgd,bsd->bgts") * (D ** -0.5)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return mm(p, v, "bgts,bsd->btgd")


def layer(x, lw, sz: Sizes, mm):
    """One decoder block: x [B, T, d] float32."""
    B, T = x.shape[:2]
    H, K, D = sz.n_heads, sz.n_kv_heads, sz.head_dim
    h = rms_norm(x, lw["norm_attn"], sz.rms_eps)
    q = mm(h, lw["wq"], "btd,dhk->bthk")
    k = mm(h, lw["wk"], "btd,dhk->bthk")
    v = mm(h, lw["wv"], "btd,dhk->bthk")
    if sz.lora_rank > 0:
        s = sz.lora_alpha / sz.lora_rank
        q = q + s * mm(mm(h, lw["q_a"], "btd,dr->btr"), lw["q_b"],
                       "btr,rhk->bthk")
        v = v + s * mm(mm(h, lw["v_a"], "btd,dr->btr"), lw["v_b"],
                       "btr,rhk->bthk")
    q, k = rope(q, sz.rope_theta), rope(k, sz.rope_theta)
    G = H // K
    qg = q.reshape(B, T, K, G, D).transpose(2, 0, 1, 3, 4)  # [K,B,T,G,D]
    kg, vg = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)  # [K,B,T,D]
    # One KV head at a time: the [G, T, T] scores of all heads at once
    # would not fit beside the weights at T = 4096. (The loop closes over
    # no weight: XLA hoists what a loop does not change, and every
    # layer's float32 copy at once does not fit.)
    # Checkpointed: a backward pass recomputes one head's scores
    # instead of keeping every head's.
    one_head = jax.checkpoint(lambda a: _attention_one(a[0], a[1], a[2], mm))
    og = lax.map(one_head, (qg, kg, vg))                    # [K,B,T,G,D]
    o = og.transpose(1, 2, 0, 3, 4).reshape(B, T, H, D)
    x = x + mm(o, lw["wo"], "bthk,hkd->btd")
    h = rms_norm(x, lw["norm_mlp"], sz.rms_eps)
    gate = jax.nn.silu(mm(h, lw["w_gate"], "btd,df->btf"))
    up = mm(h, lw["w_up"], "btd,df->btf")
    return x + mm(gate * up, lw["w_down"], "btf,fd->btd")


def trunk(w: dict, tokens, sz: Sizes, mm=f32_matmul, remat=False):
    """tokens [B, T] -> the last block's output [B, T, d] float32."""
    one_layer = partial(layer, sz=sz, mm=mm)
    if remat:
        one_layer = jax.checkpoint(one_layer)
    x = w["embed"][tokens].astype(F32)
    for lw in w["layers"]:
        x = one_layer(x, lw)
    return x


def head(w: dict, x, sz: Sizes, mm=f32_matmul):
    """x [..., T, d] -> logits [..., T, V] float32."""
    return mm(rms_norm(x, w["norm_f"], sz.rms_eps), w["head"],
              "...td,dv->...tv")


def forward(w: dict, tokens, sz: Sizes, mm=f32_matmul):
    """tokens [B, T] -> logits [B, T, V] float32."""
    return head(w, trunk(w, tokens, sz, mm), sz, mm)


def lm_loss(logits, tokens):
    """Mean next-token cross-entropy over tokens[:, 1:]."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


# ---- training: loss, adapter gradients, AdamW --------------------------

def split_adapters(w: dict):
    """(frozen weights, adapters) with adapters as float32 leaves."""
    frozen = dict(w, layers=[{k: v for k, v in lw.items()
                              if k not in ADAPTER_LEAVES}
                             for lw in w["layers"]])
    adapters = [{k: lw[k].astype(F32) for k in ADAPTER_LEAVES}
                for lw in w["layers"]]
    return frozen, adapters


def _merge(frozen, adapters):
    return dict(frozen, layers=[{**lw, **ad} for lw, ad in
                                zip(frozen["layers"], adapters)])


@partial(jax.jit, static_argnums=(3, 4))
def loss_and_grads(frozen, adapters, tokens, sz: Sizes, precision: str):
    mm = MATMULS[precision]

    def f(ad):
        w = _merge(frozen, ad)
        x = trunk(w, tokens, sz, mm, remat=True)
        # The head and the loss one sequence at a time, recomputed in the
        # backward pass: [T, V] float32 logits of every row at once, with
        # their softmax and its gradient, would not fit.
        row_loss = jax.checkpoint(
            lambda a: lm_loss(head(w, a[0][None], sz, mm), a[1][None]))
        return jnp.mean(lax.map(row_loss, (x, tokens)))

    return jax.value_and_grad(f)(adapters)


@jax.jit
def adamw_step(adapters, grads, mu, nu, count, lr, b1, b2, eps, wd):
    """optax.adamw's update, written out, in float32."""
    count = count + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda n, g: b2 * n + (1 - b2) * g * g,
                                nu, grads)
    c1 = 1 - b1 ** count.astype(F32)
    c2 = 1 - b2 ** count.astype(F32)
    new = jax.tree_util.tree_map(
        lambda p, m, n: p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps)
                                  + wd * p), adapters, mu, nu)
    return new, mu, nu, count


def train_reference(w: dict, batches, sz: Sizes, opt: dict,
                    precision: str = "float32"):
    """Follow ``len(batches)`` optimizer steps from the weights ``w``.

    Returns the losses, the first step's adapter gradients and the
    adapters' change over all the steps, the last two as lists (one dict
    per layer) of float32 arrays.
    """
    frozen, adapters = split_adapters(w)
    start = adapters
    zeros = jax.tree_util.tree_map(jnp.zeros_like, adapters)
    mu, nu, count = zeros, zeros, jnp.zeros((), jnp.int32)
    losses, first_grads = [], None
    for tokens in batches:
        loss, grads = loss_and_grads(frozen, adapters, jnp.asarray(tokens),
                                     sz, precision)
        if first_grads is None:
            first_grads = grads
        adapters, mu, nu, count = adamw_step(
            adapters, grads, mu, nu, count, F32(opt["learning_rate"]),
            F32(opt["b1"]), F32(opt["b2"]), F32(opt["eps"]),
            F32(opt["weight_decay"]))
        losses.append(float(loss))
    change = jax.tree_util.tree_map(lambda a, b: a - b, adapters, start)
    return losses, first_grads, change


# ---- serving: the gap of each served token under the reference ---------

@partial(jax.jit, static_argnums=(2, 3))
def _row_logits(w, row, sz: Sizes, precision: str):
    return forward(w, row[None], sz, mm=MATMULS[precision])[0]


def _served_logits(w, prompt, served, sz, pad_to, precision):
    """Logits at the positions that predict the served tokens, from ONE
    full forward pass over ``prompt + served`` (padded on the right to
    ``pad_to``; causal attention keeps the padding out of every real
    position)."""
    seq = list(prompt) + list(served)
    n, p = len(seq), len(prompt)
    if n > pad_to:
        raise ValueError(f"sequence of {n} tokens exceeds pad_to={pad_to}")
    row = np.zeros((pad_to,), np.int32)
    row[:n] = seq
    return _row_logits(w, jnp.asarray(row), sz, precision)[p - 1:n - 1]


def _gap_below_best(at, tokens):
    got = jnp.take_along_axis(at, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(at, axis=-1) - got


def served_token_gaps(w: dict, prompt, served, sz: Sizes, pad_to: int):
    """Per served token, how far its reference logit lies below the
    reference's best at that position (0 = the reference's own greedy
    choice)."""
    at = _served_logits(w, prompt, served, sz, pad_to, "float32")
    return np.asarray(_gap_below_best(at, jnp.asarray(served, jnp.int32)))


def control_token_gaps(w: dict, prompt, served, sz: Sizes, pad_to: int,
                       precision: str):
    """The control for a served model: at each position of the same prompt
    and served tokens, the token that the LOWER precision puts first, and
    how far its float32 reference logit lies below the reference's best."""
    at = _served_logits(w, prompt, served, sz, pad_to, "float32")
    low = _served_logits(w, prompt, served, sz, pad_to, precision)
    return np.asarray(_gap_below_best(at, jnp.argmax(low, axis=-1)))
