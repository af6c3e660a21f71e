"""The hybrid decoder of ibm-granite/granite-4.0-h-micro (``model_type``
granitemoehybrid with no experts): Mamba-2 layers with an attention layer
every so often (``layer_types``), each followed by a SwiGLU MLP, RMSNorm
before both, no positional term of any kind, a tied head, and four fixed
scalars: the embedding times ``embedding_multiplier``, both residual
branches times ``residual_multiplier``, the softmax scale
``attention_multiplier`` in place of 1/sqrt(head_dim), the logits divided
by ``logits_scaling``.

Everything the harness knows about this architecture is in this file
(``chipbench/README.md``, "An architecture"). A serving architecture: no
``train`` cell names it, so it defines none of the four training names.

The plain reference follows the equations of the granitemoehybrid /
Mamba-2 modelling code as remembered (no network here; the configuration
file says so under ``assumed``), float32, every projection through the
``mm`` it is given, the recurrence STEP BY STEP (``lax.scan`` over
tokens: no chunking, no cache, no batching tricks). It imports nothing of
the program. Departures from the source, all to the same function:

* the source fuses the MLP's gate and up projections into one matrix
  (``shared_mlp.input_linear``); here they are two leaves;
* the source stores the depthwise convolution as ``[conv_dim, 1, 4]``;
  here it is ``[4, conv_dim]`` (tap-major);
* ``A_log``, ``dt_bias`` and ``D`` are SEEDED here, by the rule Mamba-2
  initialises them with (A uniform in [1, 16], dt log-uniform in
  [1e-3, 1e-1] through softplus' inverse, D near 1): the decay
  ``exp(dt A)`` then spreads over (0, 1) as a trained model's does, so
  the state neither dies in a token nor never forgets, and a broken
  carry cannot pass ``correct``. ``weights.make_weights`` draws normals
  only, so the two leaves are drawn as N(0, 1) (``a_raw``, ``dt_raw``)
  and mapped through the normal's distribution function
  (``ssm_scalars``), by the program's tree and by the reference alike.

Counts read no compiled cost analysis and are multiply-adds times two
for the matrix products; the recurrence's own arithmetic is counted as
written below. What the engine holds for this architecture is two kinds
of state: the paged pool of the attention layers' keys and values, and
per SLOT the Mamba layers' recurrent state and carried convolution
inputs (``inference/kvcache.py`` ``take_slots``). ``warm`` runs on the
engine's own state with sentinel pages AND sentinel slot ids, so every
write drops; ``reachable_shapes`` lists only the programs this mix's own
lengths can form (the engine feeds such a model one row-chunk a slot a
program, so a program's buckets are the largest of its rows').
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import F32, f32_matmul, rms_norm


# ---- sizes, and the program's settings that follow from them -----------

@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    n_layers: int
    layer_types: tuple        # "mamba" | "attention", one a layer
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    ssm_conv: int
    ssm_chunk: int
    rms_eps: float
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def in_proj_dim(self) -> int:     # [z | xBC | dt]
        return self.d_inner + self.conv_dim + self.ssm_heads

    @property
    def n_mamba(self) -> int:
        return sum(k == "mamba" for k in self.layer_types)

    @property
    def n_attention(self) -> int:
        return self.n_layers - self.n_mamba


def sizes(config: dict) -> Sizes:
    """``config`` is the configuration file: the published ``config.json``
    keys at its top level. ``layer_types`` stays whole in the file; a
    depth below the published one takes its first layers."""
    c = config
    n = int(c["num_hidden_layers"])
    if c["mamba_expand"] * c["hidden_size"] \
            != c["mamba_n_heads"] * c["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is not "
                         "mamba_n_heads x mamba_d_head")
    if c["position_embedding_type"] != "nope" or c["num_local_experts"]:
        raise ValueError("this architecture has no positional term and "
                         "no experts")
    return Sizes(
        vocab=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_layers=n, layer_types=tuple(c["layer_types"][:n]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["hidden_size"]) // int(c["num_attention_heads"]),
        d_ff=int(c["shared_intermediate_size"]),
        ssm_heads=int(c["mamba_n_heads"]),
        ssm_head_dim=int(c["mamba_d_head"]),
        ssm_state=int(c["mamba_d_state"]),
        ssm_groups=int(c["mamba_n_groups"]),
        ssm_conv=int(c["mamba_d_conv"]),
        ssm_chunk=int(c["mamba_chunk_size"]),
        rms_eps=float(c["rms_norm_eps"]),
        embedding_multiplier=float(c["embedding_multiplier"]),
        attention_multiplier=float(c["attention_multiplier"]),
        residual_multiplier=float(c["residual_multiplier"]),
        logits_scaling=float(c["logits_scaling"]))


def model_overrides(config: dict) -> dict:
    """The program's ``model_overrides`` (``TransformerConfig`` fields)
    from the file's published keys, so that sizes are stated once."""
    sz = sizes(config)
    return {
        "vocab_size": sz.vocab, "d_model": sz.d_model,
        "n_layers": sz.n_layers, "layer_types": list(sz.layer_types),
        "n_heads": sz.n_heads, "n_kv_heads": sz.n_kv_heads,
        "d_ff": sz.d_ff, "max_seq_len": config["max_position_embeddings"],
        "position": "none", "tie_embeddings": config["tie_word_embeddings"],
        "rms_norm_eps": sz.rms_eps,
        "ssm_heads": sz.ssm_heads, "ssm_head_dim": sz.ssm_head_dim,
        "ssm_state": sz.ssm_state, "ssm_groups": sz.ssm_groups,
        "ssm_conv": sz.ssm_conv, "ssm_chunk": sz.ssm_chunk,
        "embedding_multiplier": sz.embedding_multiplier,
        "attention_multiplier": sz.attention_multiplier,
        "residual_multiplier": sz.residual_multiplier,
        "logits_scaling": sz.logits_scaling,
    }


def at_depth(config: dict, depth: int) -> dict:
    """The configuration at another depth: its first ``depth`` layers."""
    return dict(config, num_hidden_layers=depth)


# ---- the leaves, and their names in the program's tree -----------------

def _mlp_leaves(sz: Sizes) -> dict:
    d, F = sz.d_model, sz.d_ff
    return {"norm_mlp": ((d,), None),
            "w_gate": ((d, F), d ** -0.5), "w_up": ((d, F), d ** -0.5),
            "w_down": ((F, d), F ** -0.5)}


def _layer_leaves(sz: Sizes, kind: str) -> dict:
    """Per-layer leaf -> (shape, standard deviation; None = a scale near
    1). Projections are N(0, 1/fan_in)."""
    d = sz.d_model
    if kind == "attention":
        H, K, D = sz.n_heads, sz.n_kv_heads, sz.head_dim
        mixer = {"wq": ((d, H, D), d ** -0.5), "wk": ((d, K, D), d ** -0.5),
                 "wv": ((d, K, D), d ** -0.5),
                 "wo": ((H, D, d), (H * D) ** -0.5)}
    else:
        di, Hm, Kc = sz.d_inner, sz.ssm_heads, sz.ssm_conv
        mixer = {"w_in": ((d, sz.in_proj_dim), d ** -0.5),
                 "conv_w": ((Kc, sz.conv_dim), Kc ** -0.5),
                 "conv_b": ((sz.conv_dim,), 0.2),
                 "a_raw": ((Hm,), 1.0), "dt_raw": ((Hm,), 1.0),
                 "skip": ((Hm,), None), "norm_ssm": ((di,), None),
                 "w_out": ((di, d), di ** -0.5)}
    return {"norm_mix": ((d,), None), **mixer, **_mlp_leaves(sz)}


# The embedding's standard deviation. The table is the head too (tied),
# so a token's own embedding, which the residual stream still carries at
# the last block, scores against itself: at N(0, 1) that one logit is
# some 200 standard deviations above the rest, every reply repeats its
# prompt's last token whatever the layers compute, and no fault in a
# layer could change a served token. At 1/128 the embedding (times
# ``embedding_multiplier``) enters the first block at about the size of
# one residual branch and the own-token logit lies two to three standard
# deviations up: a candidate, as in a trained model, and the context
# decides. The first operation on the stream is an RMSNorm, so only this
# ratio matters, not the scale.
EMBED_STD = 1.0 / 128


def leaf_shapes(sz: Sizes) -> dict:
    """{"top": {leaf: (shape, std)}, "layers": [{leaf: (shape, std)}]}:
    what ``weights.make_weights`` draws. The embedding is the head too
    (tied)."""
    return {"top": {"embed": ((sz.vocab, sz.d_model), EMBED_STD),
                    "norm_f": ((sz.d_model,), None)},
            "layers": [_layer_leaves(sz, kind) for kind in sz.layer_types]}


def ssm_scalars(lw: dict) -> tuple:
    """(A_log, dt_bias) of a Mamba layer from its two N(0, 1) leaves, in
    the leaves' dtype: A uniform in [1, 16], dt log-uniform in
    [1e-3, 1e-1] and put through softplus' inverse (Mamba-2's own
    initialisation), with the normal's distribution function as the
    uniform."""
    dtype = lw["a_raw"].dtype
    uniform = lambda raw: 0.5 * (1.0 + lax.erf(raw.astype(F32)
                                               / math.sqrt(2.0)))
    a = 1.0 + 15.0 * uniform(lw["a_raw"])
    dt = jnp.exp(math.log(1e-3)
                 + uniform(lw["dt_raw"]) * math.log(1e-1 / 1e-3))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    return jnp.log(a).astype(dtype), dt_bias.astype(dtype)


def to_program_tree(w: dict) -> dict:
    """Canonical weights -> the flax parameter tree of the program's
    ``Transformer`` (names are load-bearing there)."""
    out = {"embedder": {"embedding": w["embed"]},
           "norm_f": {"scale": w["norm_f"]}}
    for i, lw in enumerate(w["layers"]):
        layer = {"norm_attn": {"scale": lw["norm_mix"]},
                 "norm_mlp": {"scale": lw["norm_mlp"]},
                 "mlp": {"gate_proj": {"kernel": lw["w_gate"]},
                         "up_proj": {"kernel": lw["w_up"]},
                         "down_proj": {"kernel": lw["w_down"]}}}
        if "wq" in lw:
            layer["attn"] = {"q_proj": {"kernel": lw["wq"]},
                             "k_proj": {"kernel": lw["wk"]},
                             "v_proj": {"kernel": lw["wv"]},
                             "o_proj": {"kernel": lw["wo"]}}
        else:
            a_log, dt_bias = ssm_scalars(lw)
            layer["mamba"] = {"in_proj": {"kernel": lw["w_in"]},
                              "conv_kernel": lw["conv_w"],
                              "conv_bias": lw["conv_b"],
                              "A_log": a_log, "dt_bias": dt_bias,
                              "D": lw["skip"], "norm_scale": lw["norm_ssm"],
                              "out_proj": {"kernel": lw["w_out"]}}
        out[f"layer_{i}"] = layer
    return out


# ---- the plain reference's blocks --------------------------------------

def attention_mixer(h, lw, sz: Sizes, mm):
    """softmax(q k^T * attention_multiplier + causal) v: grouped queries,
    no rotation, no positional term. h [B, T, d] float32."""
    B, T = h.shape[:2]
    H, K, D = sz.n_heads, sz.n_kv_heads, sz.head_dim
    q = mm(h, lw["wq"], "btd,dhk->bthk").reshape(B, T, K, H // K, D)
    k = mm(h, lw["wk"], "btd,dhk->bthk")
    v = mm(h, lw["wv"], "btd,dhk->bthk")
    s = mm(q, k, "btkgd,bskd->bkgts") * sz.attention_multiplier
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = mm(p, v, "bkgts,bskd->btkgd").reshape(B, T, H, D)
    return mm(o, lw["wo"], "bthk,hkd->btd")


def mamba_mixer(u, lw, sz: Sizes, mm):
    """The Mamba-2 mixer, token by token. u [B, T, d] float32.

    [z | xBC | dt] = u W_in; xBC_t = silu(b + sum_j w[j] xBC_{t-3+j}),
    zeros before the sequence; [x | B | C] = xBC; dt = softplus(dt +
    dt_bias); A = -exp(A_log); h_t = exp(dt_t A) h_{t-1} + dt_t x_t
    (outer) B_t; y_t = h_t C_t + D x_t; out = (RMSNorm over each group of
    y * silu(z)) * w, through W_out. The recurrence and its read-out are
    elementwise float32 whatever ``mm`` is: the configuration states a
    float32 state."""
    B_, T = u.shape[:2]
    Hm, P, N, G, Kc = (sz.ssm_heads, sz.ssm_head_dim, sz.ssm_state,
                       sz.ssm_groups, sz.ssm_conv)
    di, gn = sz.d_inner, sz.ssm_groups * sz.ssm_state
    zxbcdt = mm(u, lw["w_in"], "btd,de->bte")
    z, xbc, dt = jnp.split(zxbcdt, [di, di + sz.conv_dim], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (Kc - 1, 0), (0, 0)))
    conv_w = lw["conv_w"].astype(F32)
    xbc = jax.nn.silu(lw["conv_b"].astype(F32) + sum(
        padded[:, j:j + T] * conv_w[j] for j in range(Kc)))
    x, Bm, Cm = jnp.split(xbc, [di, di + gn], axis=-1)
    x = x.reshape(B_, T, Hm, P)
    per_head = lambda a: jnp.repeat(a.reshape(B_, T, G, N), Hm // G, axis=2)
    Bm, Cm = per_head(Bm), per_head(Cm)                    # [B, T, Hm, N]
    a_log, dt_bias = ssm_scalars(lw)
    A = -jnp.exp(a_log.astype(F32))
    dt = jax.nn.softplus(dt + dt_bias.astype(F32))         # [B, T, Hm]

    def step(h, at):
        x_t, b_t, c_t, dt_t = at
        h = (jnp.exp(dt_t * A)[:, :, None, None] * h
             + (dt_t[:, :, None] * x_t)[:, :, :, None] * b_t[:, :, None, :])
        return h, jnp.sum(h * c_t[:, :, None, :], axis=-1)

    tokens_first = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = lax.scan(step, jnp.zeros((B_, Hm, P, N), F32),
                    (tokens_first(x), tokens_first(Bm), tokens_first(Cm),
                     tokens_first(dt)))
    y = jnp.moveaxis(y, 0, 1) + lw["skip"].astype(F32)[:, None] * x
    g = (y.reshape(B_, T, di) * jax.nn.silu(z)).reshape(B_, T, G, di // G)
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                      + sz.rms_eps)
    g = g.reshape(B_, T, di) * lw["norm_ssm"].astype(F32)
    return mm(g, lw["w_out"], "bte,ed->btd")


def layer(x, lw, sz: Sizes, mm):
    """One block: x <- x + r mixer(RMSNorm(x)); x <- x + r MLP(RMSNorm(x))
    with r = ``residual_multiplier``. x [B, T, d] float32."""
    h = rms_norm(x, lw["norm_mix"], sz.rms_eps)
    mixed = (attention_mixer(h, lw, sz, mm) if "wq" in lw
             else mamba_mixer(h, lw, sz, mm))
    x = x + sz.residual_multiplier * mixed
    h = rms_norm(x, lw["norm_mlp"], sz.rms_eps)
    gate = jax.nn.silu(mm(h, lw["w_gate"], "btd,df->btf"))
    up = mm(h, lw["w_up"], "btd,df->btf")
    return x + sz.residual_multiplier * mm(gate * up, lw["w_down"],
                                           "btf,fd->btd")


def trunk(w: dict, tokens, sz: Sizes, mm=f32_matmul, remat=False):
    """tokens [B, T] -> the last block's output [B, T, d] float32."""
    one_layer = partial(layer, sz=sz, mm=mm)
    if remat:
        one_layer = jax.checkpoint(one_layer)
    x = sz.embedding_multiplier * w["embed"][tokens].astype(F32)
    for lw in w["layers"]:
        x = one_layer(x, lw)
    return x


def head(w: dict, x, sz: Sizes, mm=f32_matmul):
    """x [..., T, d] -> logits [..., T, V] float32: the tied embedding,
    divided by ``logits_scaling``."""
    return mm(rms_norm(x, w["norm_f"], sz.rms_eps), w["embed"],
              "...td,vd->...tv") / sz.logits_scaling


# ---- operations and bytes from shapes ----------------------------------

def mlp_params(sz: Sizes) -> int:
    return 3 * sz.d_model * sz.d_ff


def attention_matmul_params(sz: Sizes) -> int:
    d, H, K, D = sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim
    return d * H * D + 2 * d * K * D + H * D * d


def mamba_matmul_params(sz: Sizes) -> int:
    """The two projections of a Mamba mixer (the weights a token
    multiplies; the convolution and the per-head scalars apart)."""
    return sz.d_model * sz.in_proj_dim + sz.d_inner * sz.d_model


def head_params(sz: Sizes) -> int:
    return sz.d_model * sz.vocab


def matmul_params(sz: Sizes) -> int:
    """Every weight a token multiplies, the tied head included."""
    return (sz.n_mamba * (mamba_matmul_params(sz) + mlp_params(sz))
            + sz.n_attention * (attention_matmul_params(sz)
                                + mlp_params(sz))
            + head_params(sz))


def parameters(sz: Sizes) -> int:
    """All parameters of the model (the tied table once)."""
    small_mamba = (sz.ssm_conv * sz.conv_dim + sz.conv_dim
                   + 3 * sz.ssm_heads + sz.d_inner)
    return (matmul_params(sz) + sz.n_mamba * small_mamba
            + sz.n_layers * 2 * sz.d_model + sz.d_model)


def weight_bytes(sz: Sizes, bytes_per_param: int = 2) -> int:
    """Bytes a decode step has to stream: every block, the convolutions,
    and the tied table once, as the head."""
    return bytes_per_param * (matmul_params(sz) + sz.n_mamba
                              * (sz.ssm_conv + 1) * sz.conv_dim)


def attention_flops(sz: Sizes, n_query: int, n_keys: float) -> float:
    """QK^T and PV of one attention layer."""
    return 2 * 2 * sz.n_heads * sz.head_dim * n_query * n_keys


def ssm_flops_per_token(sz: Sizes) -> float:
    """One Mamba layer's recurrence for one token, as the equations have
    it: per state element a decay multiply, the outer product's
    multiply-add and the read-out's multiply-add (5 operations), and the
    convolution's taps."""
    state = sz.ssm_heads * sz.ssm_head_dim * sz.ssm_state
    return 5 * state + 2 * sz.ssm_conv * sz.conv_dim


def forward_flops_per_token(sz: Sizes, mean_keys: float) -> float:
    """One token through every block and the head, seeing ``mean_keys``
    keys in each ATTENTION layer (the Mamba layers see a state, whatever
    the context)."""
    return (2 * matmul_params(sz)
            + sz.n_attention * attention_flops(sz, 1, mean_keys)
            + sz.n_mamba * ssm_flops_per_token(sz))


def kv_bytes_per_token(sz: Sizes, bytes_per_el: int = 2) -> int:
    return 2 * sz.n_attention * sz.n_kv_heads * sz.head_dim * bytes_per_el


def state_bytes_per_row(sz: Sizes, state_bytes_per_el: int = 4,
                        conv_bytes_per_el: int = 2) -> dict:
    """What one sequence holds outside the pages: the recurrent state
    (float32) and the carried convolution inputs of every Mamba layer."""
    return {"ssm": sz.n_mamba * sz.ssm_heads * sz.ssm_head_dim
            * sz.ssm_state * state_bytes_per_el,
            "conv": sz.n_mamba * (sz.ssm_conv - 1) * sz.conv_dim
            * conv_bytes_per_el}


def decode_step_cost(sz: Sizes, rows: float, mean_context: float,
                     record: dict = None) -> dict:
    """One decode step over ``rows`` live sequences: every weight once,
    each row's recurrent state and carried convolution inputs read and
    written once, its keys and values in the attention layers read once.
    ``state_bytes`` is the part of ``bytes`` that is per-slot state."""
    held = state_bytes_per_row(sz)
    state = rows * 2 * (held["ssm"] + held["conv"])
    return {"flops": rows * forward_flops_per_token(sz, mean_context),
            "bytes": (weight_bytes(sz) + state
                      + rows * mean_context * kv_bytes_per_token(sz)),
            "state_bytes": state}


# ---- the engine's programs: which a mix reaches, and their signatures --
# Two kinds of state: the paged pool (block table, sentinel page) and the
# slot leaves (slot ids, sentinel slot). The programs' signatures are the
# dense decoder's.

def _length_pairs(mix_params: dict) -> set:
    """The (prompt, output) length pairs this mix can send: its pool's,
    which are the same for every seed."""
    from chipbench import traffic

    return {(len(r["prompt"]), r["max_new_tokens"])
            for r in traffic.request_mix(mix_params, 0, 2)}


def _closed_under_max(points: set) -> set:
    """Every componentwise maximum of a subset of ``points``."""
    out = set(points)
    while True:
        more = {(max(a[0], b[0]), max(a[1], b[1]))
                for a in out for b in out} - out
        if not more:
            return out
        out |= more


def reachable_shapes(engine, mix_params: dict) -> tuple:
    """The (nb, T, W) prefill and (nb, W) decode programs that requests
    of this mix can form. The engine feeds a model with slot state ONE
    row-chunk a slot a program, from position 0 (no prefix trie): a row
    is the j-th chunk of one of the mix's prompts, and a program's T and
    W are the largest of its rows', bucketed; any number of slots can be
    mid-prefill or live at once."""
    from serverless_learn_tpu.inference.batching import _bucket
    from serverless_learn_tpu.inference.continuous import _wbucket
    from serverless_learn_tpu.inference.kvcache import pages_for

    ps, chunk, C = engine._ps, engine.prefill_chunk, engine.chunk_size
    t_cap = _bucket(chunk, floor=1)
    w_of = lambda tokens: min(_wbucket(pages_for(tokens, ps)),
                              engine._max_pages)
    pre, dec = set(), set()
    for p_len, o_len in _length_pairs(mix_params):
        for start in range(0, p_len, chunk):
            tk = min(chunk, p_len - start)
            pre.add((min(_bucket(tk, floor=8), t_cap), w_of(start + tk)))
        for k in range(-(-(o_len - 1) // C)):       # chunks it is owed
            dec.add(w_of(min(p_len + (k + 1) * C, p_len + o_len)))
    nbs = sorted({_bucket(n, floor=1)
                  for n in range(1, engine.max_slots + 1)})
    return ([(nb, T, W) for nb in nbs
             for T, W in sorted(_closed_under_max(pre))],
            [(nb, W) for nb in nbs for W in sorted(dec)])


def _prefill_args(engine, nb: int, T: int, W: int, make) -> tuple:
    """The twelve arguments of a prefill program after ``(params, pages,
    vecs)``: ``make(shape, dtype, fill)`` builds each."""
    sent, M = engine._pool.sentinel, engine.max_slots
    i32, row = jnp.int32, (nb,)
    return (make((nb, W), i32, sent), make(row, i32, 0),
            make((nb, T), i32, 0), make(row, i32, 0), make(row, i32, M),
            make(row, jnp.bool_, False), make(row, jnp.float32, 0),
            make(row, i32, 0), make(row, i32, -1), make(row, jnp.uint32, 0),
            make(row, i32, sent), make(row, i32, sent))


def warm(engine, mix_params: dict) -> int:
    """Run every reachable program once, on the engine's OWN state: all
    table entries are sentinel pages and all slot ids sentinel slots, so
    every write drops, to the pool and to the slot leaves alike."""
    sent, M = engine._pool.sentinel, engine.max_slots
    prefill, decode = reachable_shapes(engine, mix_params)
    st = engine._state
    full = lambda shape, dtype, fill: jnp.full(shape, fill, dtype)
    for nb, W in decode:
        st["pages"], st["vecs"], toks = engine._paged_chunk_jit(nb, W)(
            engine.params, st["pages"], st["vecs"],
            full((nb, W), jnp.int32, sent), full((nb,), jnp.int32, M))
    for nb, T, W in prefill:
        st["pages"], st["vecs"], toks = engine._paged_prefill_jit(nb, T, W)(
            engine.params, st["pages"], st["vecs"],
            *_prefill_args(engine, nb, T, W, full))
    jax.block_until_ready(toks)
    return len(prefill) + len(decode)


def lower_largest(engine, params, mix_params: dict, sharding) -> list:
    """[(name, lowered program)]: the decode chunk and the prefill chunk
    at the largest buckets the mix reaches, lowered on shapes alone for
    ``rehearse.py`` (``engine`` has no device state; ``params`` are
    shapes)."""
    from serverless_learn_tpu.inference import kvcache
    from serverless_learn_tpu.inference.generate import init_cache

    M = engine.max_slots
    vec = lambda dt: jnp.zeros((M,), dt)
    state = jax.eval_shape(lambda: {
        "pages": kvcache.split_cache(init_cache(engine._pmod, M))[0],
        "vecs": {"next_tok": vec(jnp.int32), "pos": vec(jnp.int32),
                 "done": vec(jnp.bool_), "temp": vec(jnp.float32),
                 "topk": vec(jnp.int32), "eos": vec(jnp.int32),
                 "seed": vec(jnp.uint32), "ci": vec(jnp.int32)}})
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        state)
    prefill, decode = reachable_shapes(engine, mix_params)
    shaped = lambda shape, dtype, fill=None: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)
    nb, W = decode[-1]
    out = [(f"decode chunk nb={nb} W={W}", engine._paged_chunk_jit(
        nb, W).lower(params, state["pages"], state["vecs"],
                     shaped((nb, W), jnp.int32), shaped((nb,), jnp.int32)))]
    nb, T, W = prefill[-1]
    out.append((f"prefill chunk nb={nb} T={T} W={W}",
                engine._paged_prefill_jit(nb, T, W).lower(
        params, state["pages"], state["vecs"],
        *_prefill_args(engine, nb, T, W, shaped))))
    return out
