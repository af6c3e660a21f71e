"""Speculative decoding: draft K tokens cheaply, verify in ONE target pass.

Round-5 perf work on the serving surface. This repo MEASURED that small-
model decode on this chip is dispatch/bandwidth-bound, not FLOP-bound
(`ops/pallas/quant_matmul.py`: ~30% of HBM bandwidth at 1B scale; int8's
halved bytes bought ~nothing). The lever that DOES attack that regime is
sequential-step count: speculative decoding runs a cheap DRAFT model
autoregressively for K tokens, then scores all K in ONE target-model
forward (`extend` mode, `models/transformer.py`) — the target's weights
stream from HBM once per accepted-run instead of once per token. Greedy
verification keeps the output EXACTLY equal to plain greedy decode of
the target (each emitted token is argmax of the target's logits given
the same prefix — pinned by `tests/test_speculative.py`), so speed is
the only thing at stake, never correctness.

TPU shape discipline: the whole generate loop is ONE jit — a
`lax.while_loop` whose body runs the draft's K+1-step `lax.scan`, the
target's single [B, K+1] extend forward, vectorized accept logic, and
per-row KV-cache rollback. Rollback is free by construction: the cache
index is a per-row VECTOR (`cache_index`), so "un-consuming" rejected
tokens is one `.at[].set` of indices — entries beyond the index are dead
under the `<= index` attention mask and get overwritten by the next
append. No host round trips between chunks; static shapes throughout.

Acceptance (and therefore speedup) depends on draft/target agreement,
which is a property of the WEIGHTS: random-init checkpoints agree at
chance level, trained draft/target pairs at the literature's 60-90%.
The bench row reports the measured acceptance next to tokens/s so the
number can't flatter (`benchmarks/ladder.py --rows spec`).

Greedy only: sampled speculative decoding needs the rejection-sampling
correction to stay distribution-exact; submit temperature=0 or use
``generate``.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


def prefix_draft(module, params, n_layers: int):
    """(draft_module, draft_params): the target's own first ``n_layers``
    blocks plus its embedder/norm/head — the zero-extra-weights
    self-speculative draft. Single home for the ``layer_{i}`` slicing
    convention (CLI and bench both build drafts through here)."""
    import dataclasses

    if not 1 <= n_layers < module.cfg.n_layers:
        raise ValueError(
            f"draft_layers must be in [1, {module.cfg.n_layers - 1}] "
            f"(target has {module.cfg.n_layers} layers), got {n_layers}")
    draft = type(module)(dataclasses.replace(module.cfg,
                                             n_layers=n_layers))
    dparams = {k: v for k, v in params.items()
               if not k.startswith("layer_")
               or int(k.split("_")[1]) < n_layers}
    return draft, dparams


def _set_cache_index(cache, new_index):
    """Roll every layer's per-row cache index to ``new_index`` [B]."""
    def fix(path, leaf):
        if str(getattr(path[-1], "key", "")) == "cache_index":
            return new_index.astype(leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


@partial(jax.jit, static_argnums=(0, 2, 5, 6))
def _speculate_jit(target, tparams, draft, dparams, prompt,
                   max_new_tokens: int, K: int, prompt_lengths=None):
    """Returns (new_tokens [B, max_new], accepted_total [B], rounds)."""
    from serverless_learn_tpu.inference.generate import init_cache

    B, P = prompt.shape
    L = max_new_tokens + K + 1  # margin: clamped junk writes stay >= max_new

    # -- prompt prefill, both models --------------------------------------
    t_cache = init_cache(target, B)
    d_cache = init_cache(draft, B)
    t_logits, upd = target.apply(
        {"params": tparams, "cache": t_cache}, prompt,
        prefill=True, mutable=["cache"], seq_lengths=prompt_lengths)
    t_cache = upd["cache"]
    _, upd = draft.apply(
        {"params": dparams, "cache": d_cache}, prompt,
        prefill=True, mutable=["cache"], seq_lengths=prompt_lengths)
    d_cache = upd["cache"]
    if prompt_lengths is None:
        last_logits = t_logits[:, -1]
    else:
        last_logits = jnp.take_along_axis(
            t_logits, (prompt_lengths - 1)[:, None, None], axis=1)[:, 0]
    # First emitted token comes straight off the target's prefill logits.
    # Invariant from here on: both caches contain every token EXCEPT
    # ``last`` (the newest emitted token, not yet fed to either model).
    last = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    out = jnp.zeros((B, L), jnp.int32)
    out = out.at[:, 0].set(last)
    count = jnp.ones((B,), jnp.int32)

    def draft_step(carry, _):
        cache, tok = carry
        logits, upd = draft.apply(
            {"params": dparams, "cache": cache}, tok[:, None],
            decode=True, mutable=["cache"])
        nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        return (upd["cache"], nxt), nxt

    def body(state):
        (t_cache, d_cache, last, out, count, accepted_total,
         drafted_total, rounds) = state
        base = _cache_index_of(t_cache)  # [B] — tokens before ``last``

        # Draft K+1 feeds (last, d1..dK) so the draft's cache holds dK
        # too when everything accepts; the final sample is discarded.
        (d_cache, _), d_full = jax.lax.scan(
            draft_step, (d_cache, last), None, length=K + 1)
        d_full = jnp.swapaxes(d_full, 0, 1)  # [B, K+1] = d1..d_{K+1}
        d_toks = d_full[:, :K]

        # ONE target forward scores last + all K drafts.
        fed = jnp.concatenate([last[:, None], d_toks], axis=1)  # [B, K+1]
        logits, upd = target.apply(
            {"params": tparams, "cache": t_cache}, fed,
            extend=True, mutable=["cache"])
        t_cache = upd["cache"]
        t_pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, K+1]

        # a_b = length of the agreeing draft prefix; emit d1..d_a plus
        # the target's own next token (the classic free bonus token).
        agree = (d_toks == t_pred[:, :K])
        a = jnp.sum(jnp.cumprod(agree.astype(jnp.int32), axis=1), axis=1)
        # Acceptance accounting only while a row is still live: finished
        # rows keep decoding (static batch) and a fast row's
        # post-completion agrees would flatter the published stat.
        live = count < max_new_tokens
        bonus = jnp.take_along_axis(t_pred, a[:, None], axis=1)[:, 0]
        emit = jnp.where(
            (jnp.arange(K + 1)[None, :] < a[:, None]), d_toks_pad(d_toks),
            jnp.where(jnp.arange(K + 1)[None, :] == a[:, None],
                      bonus[:, None], 0))

        # Append: junk beyond a+1 lands at offsets the NEXT write covers
        # (and the L = max_new + K + 1 margin absorbs the clamped tail).
        out = jax.vmap(
            lambda row, e, c: jax.lax.dynamic_update_slice(row, e, (c,))
        )(out, emit, count)
        count = count + a + 1

        # Roll both caches back to the accepted history: everything
        # except the new ``last`` (= bonus) is consumed.
        new_index = base + 1 + a
        t_cache = _set_cache_index(t_cache, new_index)
        d_cache = _set_cache_index(d_cache, new_index)
        return (t_cache, d_cache, bonus, out, count,
                accepted_total + jnp.where(live, a, 0),
                drafted_total + jnp.where(live, K, 0), rounds + 1)

    def d_toks_pad(d_toks):
        return jnp.concatenate(
            [d_toks, jnp.zeros((d_toks.shape[0], 1), jnp.int32)], axis=1)

    def cond(state):
        return jnp.min(state[4]) < max_new_tokens

    state = (t_cache, d_cache, last, out, count,
             jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
             jnp.zeros((), jnp.int32))
    (_, _, _, out, _, accepted_total, drafted_total,
     rounds) = jax.lax.while_loop(cond, body, state)
    return out[:, :max_new_tokens], accepted_total, drafted_total, rounds


def _cache_index_of(cache):
    """One layer's [B] cache index (all layers agree by construction)."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if str(getattr(path[-1], "key", "")) == "cache_index":
            return leaf
    raise ValueError("cache has no cache_index leaf")


def speculative_generate(
    target, tparams, draft, dparams,
    prompt: jax.Array,  # [B, P] int32
    max_new_tokens: int,
    K: int = 4,
    eos_id: Optional[int] = None,
    prompt_lengths: Optional[jax.Array] = None,
):
    """Greedy continuation of ``prompt`` under ``target``, drafted by
    ``draft`` — byte-identical to ``generate(target, ...)`` greedy.

    Returns ``(tokens [B, P + max_new], stats)`` where stats carries the
    measured ``acceptance`` (mean accepted drafts per round / K) and
    ``rounds``. EOS handling matches ``generate``'s sticky fill.
    """
    if K < 1:
        raise ValueError(f"K must be >= 1, got {K}")
    if target.cfg.vocab_size != draft.cfg.vocab_size:
        raise ValueError("draft and target must share a vocabulary")
    P = prompt.shape[1]
    if max_new_tokens <= 0:
        return prompt.astype(jnp.int32), {"acceptance": 0.0, "rounds": 0}
    for m, who in ((target, "target"), (draft, "draft")):
        if "mamba" in m.cfg.layer_types:
            # A rejected draft rolls the cache index back; a recurrent
            # state has consumed the rejected tokens and cannot follow.
            raise NotImplementedError(
                f"speculative decoding over a {who} with recurrent layers")
        if P + max_new_tokens + K > m.cfg.max_seq_len:
            raise ValueError(
                f"prompt + max_new + K ({P}+{max_new_tokens}+{K}) exceeds "
                f"{who} max_seq_len {m.cfg.max_seq_len} (the verify span "
                "transiently runs K past the final token)")
    new, accepted, drafted, rounds = _speculate_jit(
        target, tparams, draft, dparams, prompt.astype(jnp.int32),
        max_new_tokens, K, prompt_lengths)
    import numpy as np

    new = np.array(jax.device_get(new))  # copy: device_get is read-only
    if eos_id is not None:
        # Sticky-EOS fill, identical to generate's forced-eos contract.
        for b in range(new.shape[0]):
            hits = np.nonzero(new[b] == eos_id)[0]
            if hits.size:
                new[b, hits[0]:] = eos_id
    rounds = int(jax.device_get(rounds))
    accepted_np = np.asarray(jax.device_get(accepted), np.float64)
    drafted = np.asarray(jax.device_get(drafted), np.float64)
    acc = float(np.mean(accepted_np / np.maximum(drafted, 1)))
    # Draft economics on the wire (round 21): the accept rate is the
    # single knob that decides whether the draft model pays for itself,
    # and the token counters let `slt top` derive it over any window.
    from serverless_learn_tpu.telemetry import get_registry

    reg = get_registry()
    reg.gauge("slt_spec_accept_rate",
              "mean accepted-draft fraction of the last speculative "
              "generate call").set(acc)
    reg.counter("slt_spec_draft_tokens_total",
                "tokens proposed by the draft model").inc(
                    float(drafted.sum()))
    reg.counter("slt_spec_verified_tokens_total",
                "draft tokens accepted by the target verify pass").inc(
                    float(accepted_np.sum()))
    tokens = np.concatenate([np.asarray(jax.device_get(prompt)), new],
                            axis=1)
    return jnp.asarray(tokens), {"acceptance": acc, "rounds": rounds}
