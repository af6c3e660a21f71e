"""Mixture-of-experts layer with expert parallelism over the ``ep`` mesh axis.

The reference has no concept of conditional computation (its model is a flat
``repeated double``, ``src/protos/serverless_learn.proto:81-83``); this module
exists so the framework covers expert parallelism alongside dp/fsdp/tp/sp/pp
(SURVEY.md §2.9's strategy checklist).

TPU-first design — the GShard/Switch "dense dispatch" formulation rather than
gather/scatter: routing produces *static-shape* dispatch/combine tensors and
the token→expert shuffle is two einsums, which XLA partitions into all-to-alls
over the ``ep`` axis when the expert dimension is sharded. No dynamic shapes,
no sorts on the hot path; expert FFNs are batched 3-D matmuls that tile onto
the MXU. Over-capacity tokens are dropped by construction (their slot one-hot
is all-zero) and pass through on the residual branch.

Tokens are routed in *groups* (the GShard recipe): each batch row is split
into subgroups of at most ``moe_group_size`` tokens, and slot competition,
capacity, and the dispatch tensors are all per-group. Memory for the one-hot
dispatch intermediates is therefore ``O(tokens × group_size)`` — independent
of sequence length and of the global token count — and the routing cumsum
never crosses the dp-sharded batch axis (each dp shard routes its own rows:
no cross-replica slot competition, no all-reduce on dispatch), so per-device
expert compute scales down with dp. That is the TRAINING path.

Serving (``dropless``: decode, prefill, extend) drops no token and computes
no expert for a token that was not routed to it: the (token, expert) pairs
are sorted by expert, the held experts run as one grouped product over
their groups (the ``moe_gmm`` Pallas kernel where the shapes tile, else
``jax.lax.ragged_dot``), and the results go back to token order, weighted
(``dropless_experts``). Both paths route alike (``route``), and a shared
expert, where the model has one, adds its output once.
"""

from __future__ import annotations

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp


def route(logits: jax.Array, top_k: int, score: str = "softmax",
          bias=None, norm: bool = True, scale: float = 1.0):
    """Router logits [..., E] (float32) -> (scores [..., E], gate weights
    [..., K], expert ids [..., K]).

    Scores are ``softmax`` or ``sigmoid`` of the logits. The experts are
    the top ``top_k`` of the scores plus ``bias`` [E] where one is given:
    the bias steers the choice only. The gate weights are the chosen
    experts' scores, renormalised over the k where ``norm`` holds, times
    ``scale``."""
    logits = logits.astype(jnp.float32)
    scores = (jax.nn.softmax(logits, -1) if score == "softmax"
              else jax.nn.sigmoid(logits))
    picked = scores if bias is None else scores + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(picked, top_k)
    gate_w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm:
        gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        gate_w = gate_w * scale
    return scores, gate_w, idx


def dropless_experts(x: jax.Array, gate_w: jax.Array, idx: jax.Array,
                     w_gate: jax.Array, w_up: jax.Array,
                     w_down: jax.Array) -> jax.Array:
    """Every token through exactly its routed SwiGLU experts, none
    dropped. x [N, D], gate_w / idx [N, K], expert weights [E, D, F] and
    [E, F, D] -> [N, D] float32.

    The N·K (token, expert) pairs are sorted by expert, each expert's
    rows are one group of a grouped product over the E held experts (the
    ``moe_gmm`` kernel where it tiles, else ``jax.lax.ragged_dot``), and
    the rows go back to token order, summed under their gate weights."""
    N, D = x.shape
    K, E = idx.shape[1], w_gate.shape[0]
    with jax.named_scope("moe.dispatch"):
        flat = idx.reshape(-1)
        order = jnp.argsort(flat, stable=True)
        rows = jnp.take(x, order // K, axis=0)               # [N*K, D]
        sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        h = (jax.nn.silu(_grouped(rows, w_gate, sizes))
             * _grouped(rows, w_up, sizes))
        out = _grouped(h, w_down, sizes)                      # [N*K, D]
    with jax.named_scope("moe.combine"):
        back = jnp.take(out, jnp.argsort(order), axis=0).reshape(N, K, D)
        return jnp.einsum("nk,nkd->nd", gate_w,
                          back.astype(jnp.float32))


def top_k_routing(router_logits: jax.Array, n_experts: int, top_k: int,
                  capacity: int, **routing):
    """Static-shape grouped top-k routing with per-group expert capacity.

    Args:
      router_logits: [G, S, E] float32 — G independent routing groups of S
        tokens each (callers use one group per batch row).
      capacity: slots per expert per group (C).
      routing: ``route``'s keywords (score, bias, norm, scale).

    Returns:
      dispatch: [G, S, E, C] {0,1} — token (g, s) occupies slot c of expert e.
      combine:  [G, S, E, C] float32 — dispatch weighted by the (renormalized)
        gate probability.
      aux: scalar load-balance loss (Switch-style: E * Σ_e frac_e · prob_e,
        computed PER GROUP and averaged over groups). Per-group computation
        is the GShard formulation and — unlike a joint mean over all groups —
        is *linear in any even batch split*: splitting the G groups into M
        equal microbatches and averaging their per-microbatch aux reproduces
        the full-batch value exactly, which is what makes pipelined MoE
        (parallel/pipeline.py's per-microbatch aux sum / M) match the dp
        semantics bit-for-bit instead of approximately.
    """
    G, S, E = router_logits.shape
    probs, gate_w, gate_idx = route(router_logits, top_k, **routing)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # [G, S, K, E]

    # Slot assignment within each group: all 1st choices take priority over
    # 2nd choices, and within a choice rank tokens queue in order — arrange
    # [G, K*S, E] with k major, exclusive-cumsum over positions, undo.
    flat = jnp.swapaxes(onehot, 1, 2).reshape(G, top_k * S, E)
    pos = jnp.cumsum(flat, axis=1) - flat
    pos = jnp.swapaxes(pos.reshape(G, top_k, S, E), 1, 2)  # [G, S, K, E]

    # one_hot maps out-of-range positions (>= capacity) to all-zero rows, so
    # capacity overflow drops tokens without any branching.
    slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                          dtype=jnp.float32)  # [G, S, K, E, C]
    disp_k = onehot[..., None] * slot  # [G, S, K, E, C]
    combine = jnp.einsum("gsk,gskec->gsec", gate_w, disp_k)
    dispatch = (disp_k.sum(axis=2) > 0).astype(jnp.float32)

    # Load-balance: fraction of tokens whose FIRST choice is e, times mean
    # router prob for e; minimized (== 1) when routing is uniform. Computed
    # per group then averaged so the loss is linear in a group-aligned batch
    # split (see docstring) — a joint mean over all groups would make
    # pipelined microbatch averaging diverge from the full-batch value.
    frac = onehot[:, :, 0, :].mean(axis=1)  # [G, E]
    mean_prob = probs.mean(axis=1)  # [G, E]
    aux = n_experts * jnp.mean(jnp.sum(frac * mean_prob, axis=-1))
    return dispatch, combine, aux


def apply_with_losses(module, params, *args, **kwargs):
    """``module.apply`` that consumes the ``"losses"`` collection.

    Returns ``(out, aux)`` where ``aux`` is the sum of every sown loss (0.0
    when the model sows none). Model bundles must route ``apply`` through
    this helper so that enabling MoE via ``model_overrides`` (``n_experts``)
    can never silently drop the router load-balance loss.
    """
    out, mutables = module.apply({"params": params}, *args,
                                 mutable=["losses"], **kwargs)
    leaves = jax.tree_util.tree_leaves(mutables.get("losses", {}))
    aux = sum(jnp.sum(leaf) for leaf in leaves) if leaves else jnp.float32(0.0)
    return out, aux


def _grouped(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array) -> jax.Array:
    """``lhs [M, k]`` by ``rhs [E, k, n]`` over the contiguous row groups
    ``sizes [E]`` -> ``[M, n]``: the Pallas kernel where it tiles."""
    from serverless_learn_tpu.ops.pallas import moe_gmm

    if moe_gmm.untileable_reason(lhs, rhs) is None:
        return moe_gmm.gmm(lhs, rhs, sizes)
    return jax.lax.ragged_dot(lhs, rhs, sizes)


class MoELayer(nn.Module):
    """Drop-in MLP replacement: top-k routed SwiGLU experts of width
    ``moe_d_ff`` (or ``d_ff``), and a shared expert of width
    ``moe_shared_d_ff`` where the model has one.

    Expert weights are stacked on a leading ``[n_experts, ...]`` dim that the
    sharding rule table maps to ``ep`` (``parallel/sharding.py``); the two
    dispatch/combine einsums then induce ICI all-to-alls under GSPMD. The aux
    load-balance loss is sown into the ``"losses"`` collection — model
    bundles apply through ``apply_with_losses`` to add it to the task loss.
    ``dropless`` (serving) takes ``dropless_experts`` instead of the
    capacity dispatch and sows each token's expert ids into the
    ``"moe_route"`` collection, which a caller that makes it mutable
    reads (the serving engine counts the experts a decode step read).
    """

    cfg: "TransformerConfig"  # noqa: F821 — transformer.py's config

    @nn.compact
    def __call__(self, x: jax.Array, dropless: bool = False
                 ) -> jax.Array:  # [B, T, D] -> [B, T, D]
        cfg = self.cfg
        E, K = cfg.n_experts, cfg.moe_top_k
        F = cfg.moe_d_ff or cfg.d_ff
        # Manual expert parallelism (inside a pipeline's shard_map, where
        # GSPMD can't partition for us — round-4 pp x ep): cfg.n_experts is
        # this member's LOCAL expert count, routing runs over the GLOBAL
        # count, and two explicit lax.all_to_all calls replace the
        # partitioner-induced ones: slots split by owning expert and
        # exchanged for the other members' token slots (the literal GShard
        # schedule). Tokens here are ep-sharded batch rows (gpipe_apply
        # includes "ep" in its batch axes), so attention runs data-parallel
        # over ep and only expert compute reshuffles.
        ep = cfg.manual_ep_axis
        E_route = cfg.moe_global_experts if ep else E
        B, T, D = x.shape

        router = self.param(
            "router", nn.initializers.normal(0.02), (D, E_route),
            cfg.param_dtype)
        bias = (self.param("expert_bias", nn.initializers.zeros,
                           (E_route,), cfg.param_dtype)
                if cfg.moe_select_bias else None)
        routing = dict(score=cfg.moe_score, bias=bias,
                       norm=cfg.moe_route_norm, scale=cfg.moe_route_scale)

        if cfg.quant == "int8":
            # Weight-only int8 experts (round 5): expert tensors are the
            # BULK of a MoE model's params, so the capacity win demands
            # them. Stored int8 + per-(expert, out-channel) scale —
            # dequant is one fused multiply on the einsum's weight load;
            # params come from inference/quantize.quantize_params_int8.
            def qparam(name, shape, red_axis):
                q = self.param(name + "_q", nn.initializers.zeros, shape,
                               jnp.int8)
                s_shape = tuple(d for i, d in enumerate(shape)
                                if i != red_axis)
                s = self.param(name + "_scale", nn.initializers.ones,
                               s_shape, jnp.float32)
                return (q.astype(cfg.dtype)
                        * jnp.expand_dims(s, red_axis).astype(cfg.dtype))

            w_gate = qparam("expert_gate", (E, D, F), 1)
            w_up = qparam("expert_up", (E, D, F), 1)
            w_down = qparam("expert_down", (E, F, D), 1)
        else:
            init = nn.initializers.lecun_normal(in_axis=1, out_axis=2)
            w_gate = self.param("expert_gate", init, (E, D, F),
                                cfg.param_dtype)
            w_up = self.param("expert_up", init, (E, D, F), cfg.param_dtype)
            w_down = self.param("expert_down", init, (E, F, D),
                                cfg.param_dtype)
        shared = None
        if cfg.moe_shared_d_ff:
            from serverless_learn_tpu.models.transformer import MlpBlock

            shared = MlpBlock(dataclasses.replace(
                cfg, d_ff=cfg.moe_shared_d_ff), name="shared")

        if dropless:
            if ep:
                raise NotImplementedError(
                    "dropless routing holds every expert: serving runs no "
                    "expert-parallel pipeline")
            x2 = x.reshape(B * T, D)
            with jax.named_scope("moe.route"):
                logits = jnp.einsum("nd,de->ne", x2.astype(jnp.float32),
                                    router.astype(jnp.float32))
                _, gate_w, idx = route(logits, K, **routing)
            self.sow("moe_route", "experts", idx.reshape(B, T, K))
            y = dropless_experts(
                x2.astype(cfg.dtype), gate_w, idx, w_gate.astype(cfg.dtype),
                w_up.astype(cfg.dtype), w_down.astype(cfg.dtype))
            y = y.reshape(B, T, D)
        else:
            y = self._capacity_dispatch(x, router, routing, w_gate, w_up,
                                        w_down)
        if shared is not None:
            y = y + shared(x).astype(jnp.float32)
        return y.astype(cfg.dtype)

    def _capacity_dispatch(self, x, router, routing, w_gate, w_up, w_down):
        """The training path: static-shape grouped top-k dispatch with a
        capacity per expert and group; over-capacity tokens pass through on
        the residual. [B, T, D] -> [B, T, D] float32."""
        cfg = self.cfg
        K, ep = cfg.moe_top_k, cfg.manual_ep_axis
        E_route = cfg.moe_global_experts if ep else cfg.n_experts
        B, T, D = x.shape
        # Split each row into routing subgroups of <= moe_group_size tokens
        # (largest divisor of T that fits) so the one-hot dispatch
        # intermediates stay bounded at long sequence length.
        limit = min(cfg.moe_group_size or T, T)
        gs = max(d for d in range(1, limit + 1) if T % d == 0)
        x = x.reshape(B * (T // gs), gs, D)  # [G, S, D]
        capacity = max(1, int(cfg.moe_capacity_factor * K * gs / E_route))
        logits = jnp.einsum("btd,de->bte", x.astype(jnp.float32),
                            router.astype(jnp.float32))
        dispatch, combine, aux = top_k_routing(logits, E_route, K, capacity,
                                               **routing)
        self.sow("losses", "moe_aux", cfg.moe_aux_weight * aux)

        # Dispatch tokens to expert slots; with batch over dp and experts
        # over ep, GSPMD lowers the e-contraction to an ICI all-to-all (or
        # the manual path below issues it explicitly).
        xe = jnp.einsum("btec,btd->becd", dispatch.astype(cfg.dtype),
                        x.astype(cfg.dtype))  # [B, E_route, C, D]
        if ep:
            # -> [B * ep, E_local, C, D]: every member's slots for MY experts.
            xe = jax.lax.all_to_all(xe, ep, split_axis=1, concat_axis=0,
                                    tiled=True)
        h = nn.silu(jnp.einsum("becd,edf->becf", xe, w_gate.astype(cfg.dtype)))
        h = h * jnp.einsum("becd,edf->becf", xe, w_up.astype(cfg.dtype))
        ye = jnp.einsum("becf,efd->becd", h, w_down.astype(cfg.dtype))
        if ep:
            # Return each member's slots: -> [B, E_route, C, D].
            ye = jax.lax.all_to_all(ye, ep, split_axis=0, concat_axis=1,
                                    tiled=True)
        # Combine back to token order, gate-weighted (second all-to-all).
        y = jnp.einsum("btec,becd->btd", combine.astype(jnp.float32),
                       ye.astype(jnp.float32))
        if cfg.manual_tp_axis:
            # Row-parallel expert down-projection: each tp member holds
            # d_ff/tp of every (local) expert; partial sums combine here.
            y = jax.lax.psum(y, cfg.manual_tp_axis)
        return y.reshape(B, T, D)
