"""State-space sequence ops: the Mamba-2 (SSD) mixer's three parts.

* ``causal_conv`` — the short causal depthwise convolution, with the
  last ``K - 1`` inputs CARRIED from call to call, so that a sequence fed
  in pieces (chunked prefill, then one token a step) convolves as if it
  had been fed whole.
* ``ssd_scan`` — the selective state-space recurrence
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t``, ``y_t = h_t C_t``
  over a stretch of tokens in its chunked ("state-space dual") form:
  inside a chunk the recurrence unrolls into a masked, decay-weighted
  ``(C B^T) x`` (matrix products, which XLA places on the MXU); across
  chunks one small state is carried. It starts from a carried state and
  returns the state after its last token.
* ``ssm_step`` — the same recurrence for one token.

``jax.numpy`` only: no kernel. A token whose ``dt`` is 0 leaves the state
exactly as it was (decay ``exp(0) = 1``, input ``0``), which is how a
right-padded row stops after its real tokens and how a stretch is padded
to whole chunks. Decays, ``dt`` and the state are float32 throughout;
the products that read or write the carried state run at ``highest``
precision (on a TPU the default would round the float32 state to
bfloat16 on its way into the MXU).

Shapes: ``b`` rows, ``T`` tokens, ``H`` heads of ``P`` channels, ``G``
groups (heads of one group share B and C), ``N`` state size.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def causal_conv(x: jax.Array, carried: jax.Array, w: jax.Array,
                b: Optional[jax.Array],
                lens: Optional[jax.Array] = None):
    """``y_t = b + sum_j w[j] * in_{t-(K-1)+j}`` per channel.

    x [b, T, C]; ``carried`` [b, K-1, C]: the inputs just before ``x``
    (zeros at a sequence's start); w [K, C]; b [C] or None. ``lens`` [b]:
    real tokens of each right-padded row (None: all ``T``). Returns
    (y [b, T, C] float32, the ``K - 1`` inputs that END at each row's last
    real token, in ``carried``'s dtype)."""
    with jax.named_scope("ssm.conv"):
        K, T = w.shape[0], x.shape[1]
        full = jnp.concatenate([carried.astype(x.dtype), x], axis=1)
        wf = w.astype(F32)
        y = sum(full[:, j:j + T].astype(F32) * wf[j] for j in range(K))
        if b is not None:
            y = y + b.astype(F32)
        if lens is None:
            new = full[:, T:]
        else:
            idx = lens.astype(jnp.int32)[:, None] + jnp.arange(K - 1)[None]
            new = jnp.take_along_axis(full, idx[:, :, None], axis=1)
        return y, new.astype(carried.dtype)


def _by_group(a: jax.Array, G: int, axis: int = 3) -> jax.Array:
    """Split the head axis (axis 3 of a chunked [b, c, L, H, ...] array)
    into [G, H/G]."""
    return a.reshape(*a.shape[:axis], G, a.shape[axis] // G,
                     *a.shape[axis + 1:])


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, h0: jax.Array, chunk: int):
    """The recurrence over ``T`` tokens from the state ``h0``.

    x [b, T, H, P]; dt [b, T, H] float32, after its softplus, 0 where a
    token is padding; A [H] float32, negative; B, C [b, T, G, N];
    h0 [b, H, P, N] float32. Returns (y [b, T, H, P] float32, without the
    ``D x`` skip; the state after the last token, float32)."""
    with jax.named_scope("ssm.scan"):
        b, T, H, P = x.shape
        G, N = B.shape[2], B.shape[3]
        L = min(chunk, T)
        pad = -T % L
        if pad:
            widen = lambda a: jnp.pad(
                a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            x, dt, B, C = widen(x), widen(dt), widen(B), widen(C)
        nc = (T + pad) // L
        chunked = lambda a: a.reshape(b, nc, L, *a.shape[2:])
        x, dt, B, C = chunked(x), chunked(dt.astype(F32)), chunked(B), chunked(C)
        a = dt * A.astype(F32)                        # [b, c, L, H], <= 0
        s = jnp.cumsum(a, axis=2)                     # decay exponent to t
        # Inside a chunk: y_t += sum_{u<=t} (C_t . B_u) e^{s_t-s_u} dt_u x_u
        cb = jnp.einsum("bclgn,bcugn->bcglu", C, B,
                        preferred_element_type=F32)
        diff = s[:, :, :, None, :] - s[:, :, None, :, :]       # [b,c,t,u,H]
        keep = jnp.tril(jnp.ones((L, L), bool))[None, None, :, :, None]
        # The exponent of a masked pair is positive and may overflow:
        # mask before the exponential, not after.
        w = jnp.exp(jnp.where(keep, diff, -jnp.inf)) * dt[:, :, None, :, :]
        w = _by_group(w.transpose(0, 1, 4, 2, 3), G, axis=2)   # [b,c,G,h,t,u]
        w = w * cb[:, :, :, None]
        xg = _by_group(x, G)                                   # [b,c,L,G,h,P]
        y = jnp.einsum("bcghtu,bcughp->bctghp", w, xg.astype(F32))
        # What a chunk adds to the state by its end.
        to_end = jnp.exp(s[:, :, -1:, :] - s) * dt             # [b,c,L,H]
        xw = xg.astype(F32) * _by_group(to_end, G)[..., None]
        added = jnp.einsum("bclghp,bclgn->bcghpn", xw, B.astype(F32),
                           precision=_HI)
        through = jnp.exp(s[:, :, -1, :])                      # [b,c,H]

        def carry(h, inp):                  # h: the state ENTERING a chunk
            add, thr = inp
            return h * thr[:, :, None, None] + add, h

        h_last, h_in = lax.scan(
            carry, h0.astype(F32),
            (added.reshape(b, nc, H, P, N).swapaxes(0, 1),
             through.swapaxes(0, 1)))
        h_in = h_in.swapaxes(0, 1).reshape(b, nc, G, H // G, P, N)
        y_off = jnp.einsum("bclgn,bcghpn->bclghp", C.astype(F32), h_in,
                           precision=_HI)
        y = y + y_off * _by_group(jnp.exp(s), G)[..., None]
        return y.reshape(b, nc * L, H, P)[:, :T], h_last


def ssm_step(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, h: jax.Array):
    """One token: x [b, H, P]; dt [b, H] float32; B, C [b, G, N];
    h [b, H, P, N] float32. Returns (y [b, H, P] float32, the new state).
    Elementwise in float32: a step is bound by reading and writing the
    state, not by its arithmetic."""
    with jax.named_scope("ssm.step"):
        H, G = x.shape[1], B.shape[1]
        per_head = lambda a: jnp.repeat(a.astype(F32), H // G, axis=1)
        dt = dt.astype(F32)
        decay = jnp.exp(dt * A.astype(F32))                    # [b, H]
        dx = dt[:, :, None] * x.astype(F32)                    # [b, H, P]
        h = (h * decay[:, :, None, None]
             + dx[:, :, :, None] * per_head(B)[:, :, None, :])
        y = jnp.sum(h * per_head(C)[:, :, None, :], axis=-1)
        return y, h
