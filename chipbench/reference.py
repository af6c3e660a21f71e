"""The plain reference, as far as it is the same for every architecture:
the matrix products and their controls, RMSNorm, rotary positions, the
loss, AdamW, the loop that follows the program's first steps, and the gap
of each served token. The blocks themselves are the architecture's
(``arch/<name>.py``: ``trunk``, ``head``, ``split_trained``,
``merge_trained``), in float32 ``jax.numpy`` with no kernel, no cache and
no batching tricks. Nothing here imports anything of the program.

``matmul`` is pluggable so that the control can run the same mathematics
in the next precision down (``fp8_matmul``): ``correct`` has to reject it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

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


def lm_loss(logits, tokens):
    """Mean next-token cross-entropy over tokens[:, 1:]."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


# ---- training: loss, the trained leaves' gradients, AdamW --------------

@partial(jax.jit, static_argnums=(0, 4, 5))
def loss_and_grads(arch, frozen, trained, tokens, sz, precision: str):
    mm = MATMULS[precision]

    def f(tr):
        w = arch.merge_trained(frozen, tr)
        x = arch.trunk(w, tokens, sz, mm, remat=True)
        # The head and the loss one sequence at a time, recomputed in the
        # backward pass: [T, V] float32 logits of every row at once, with
        # their softmax and its gradient, would not fit.
        row_loss = jax.checkpoint(
            lambda a: lm_loss(arch.head(w, a[0][None], sz, mm), a[1][None]))
        return jnp.mean(lax.map(row_loss, (x, tokens)))

    return jax.value_and_grad(f)(trained)


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


def train_reference(arch, w: dict, batches, sz, opt: dict,
                    precision: str = "float32"):
    """Follow ``len(batches)`` optimizer steps from the weights ``w``.

    Returns the losses, the first step's gradients of the trained leaves
    and those leaves' change over all the steps, the last two as the
    architecture lists them (``split_trained``: dicts of float32 arrays).
    """
    frozen, trained = arch.split_trained(w)
    start = trained
    zeros = jax.tree_util.tree_map(jnp.zeros_like, trained)
    mu, nu, count = zeros, zeros, jnp.zeros((), jnp.int32)
    losses, first_grads = [], None
    for tokens in batches:
        loss, grads = loss_and_grads(arch, frozen, trained,
                                     jnp.asarray(tokens), sz, precision)
        if first_grads is None:
            first_grads = grads
        trained, mu, nu, count = adamw_step(
            trained, grads, mu, nu, count, F32(opt["learning_rate"]),
            F32(opt["b1"]), F32(opt["b2"]), F32(opt["eps"]),
            F32(opt["weight_decay"]))
        losses.append(float(loss))
    change = jax.tree_util.tree_map(lambda a, b: a - b, trained, start)
    return losses, first_grads, change


# ---- serving: the gap of each served token under the reference ---------

@partial(jax.jit, static_argnums=(0, 3, 4))
def _row_logits(arch, w, row, sz, precision: str):
    mm = MATMULS[precision]
    return arch.head(w, arch.trunk(w, row[None], sz, mm), sz, mm)[0]


def _served_logits(arch, w, prompt, served, sz, pad_to, precision):
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
    return _row_logits(arch, w, jnp.asarray(row), sz,
                       precision)[p - 1:n - 1]


def _gap_below_best(at, tokens):
    got = jnp.take_along_axis(at, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(at, axis=-1) - got


def served_token_gaps(arch, w: dict, prompt, served, sz, pad_to: int):
    """Per served token, how far its reference logit lies below the
    reference's best at that position (0 = the reference's own greedy
    choice)."""
    at = _served_logits(arch, w, prompt, served, sz, pad_to, "float32")
    return np.asarray(_gap_below_best(at, jnp.asarray(served, jnp.int32)))


def control_token_gaps(arch, w: dict, prompt, served, sz, pad_to: int,
                       precision: str):
    """The control for a served model: at each position of the same prompt
    and served tokens, the token that the LOWER precision puts first, and
    how far its float32 reference logit lies below the reference's best."""
    at = _served_logits(arch, w, prompt, served, sz, pad_to, "float32")
    low = _served_logits(arch, w, prompt, served, sz, pad_to, precision)
    return np.asarray(_gap_below_best(at, jnp.argmax(low, axis=-1)))
