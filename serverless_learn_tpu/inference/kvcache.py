"""Paged KV-cache primitives: block pool, block tables, prefix trie.

One resident KV allocation of ``max_slots`` full-length rows makes every
slot pay ``max_seq_len`` worth of HBM whether it holds 3 tokens or 3000,
keeps retired slots burning decode FLOPs until re-admission, and lets a
long prefill stall the whole decode batch. This module is the host side
of the serving engine's paged pool instead:

* :class:`BlockPool` — a free-list allocator over ``num_blocks`` page
  ids with per-block refcounts. The device arrays it indexes into live
  per attention layer (``pages_k/v [num_blocks, block_size, K * D]``: a
  token's heads are one row, ``models/transformer.py``); the SAME id
  addresses every layer's pool, so one host-side table drives all
  layers. Exhaustion raises the typed :class:`KVBlocksExhausted` —
  admission backpressure, never a crash.
* :class:`PrefixTrie` — hash-consed shared-prefix reuse. Nodes sit at
  block granularity (one node per ``block_size``-token chunk, keyed by
  the chunk's token tuple); a registered node holds its own pool
  reference, so prompt-prefix blocks outlive their first owner and later
  identical prefixes (the fleet's system prompts) map to the same
  refcounted READ-ONLY pages. Divergence mid-block is served by
  copy-on-write: lookup also reports the child block whose leading
  tokens match, and the engine copies it device-side into a fresh page
  before overwriting from the divergent offset. LRU eviction under
  ``max_blocks`` (and on-demand via :meth:`release`) keeps the cache
  from starving live admissions.
* Cache-pytree helpers (:func:`split_cache` / :func:`with_tables`) —
  the flax cache collection nests ``{pages_k, pages_v, page_tbl,
  cache_index}`` per layer; engines keep the pool leaves device-resident
  and donated while re-injecting ONE host-built table window per call
  (the compiled width ``W`` is how short sequences avoid attending over
  ``max_seq_len``).

Sharing is sound because K/V depend only on token values and absolute
positions (RoPE): identical prefixes at identical positions produce
identical K/V, and prefix pages are never written after registration —
generation appends strictly past the prompt, and the boundary
(partially-filled) prompt block is never registered.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import heapq
import itertools
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple


class KVCacheError(RuntimeError):
    """Base for paged-KV allocator errors."""


class KVBlocksExhausted(KVCacheError):
    """The pool cannot satisfy an allocation — typed admission
    backpressure: the scheduler keeps the request queued (or preempts)
    instead of crashing the dispatcher."""

    def __init__(self, need: int, free: int, total: int):
        super().__init__(
            f"KV block pool exhausted: need {need}, {free} free of {total}")
        self.need = need
        self.free = free
        self.total = total


def pages_for(n_tokens: int, block_size: int) -> int:
    """Pages needed to hold ``n_tokens`` tokens."""
    if n_tokens <= 0:
        return 0
    return -(-n_tokens // block_size)


# Round-22 fleetscope digest scheme. A chunk's hash is chained through
# its whole ancestry (h_i = blake2b(h_{i-1} || chunk_i tokens), 64-bit),
# so one hash names one exact token PREFIX — two replicas report the
# same hash iff they hold KV for the same leading tokens, and the router
# can intersect prompt hashes with ping digests without shipping tokens
# over the wire. 64 bits keeps ping payloads small; with n resident
# chunks fleet-wide the collision probability is ~n^2/2^65 (n=10^6 =>
# ~3e-8), and a collision only ever OVER-counts redundancy by one chunk.
_DIGEST_SEED = b"slt-prefix-digest-v1"


def chunk_hashes(tokens: Sequence[int], block_size: int) -> List[str]:
    """Chained 64-bit hashes (16 hex chars) of each FULL leading
    ``block_size``-token chunk of ``tokens``. Position i's hash commits
    to chunks [0, i] — the prefix, not just the chunk."""
    out: List[str] = []
    prev = _DIGEST_SEED
    bs = block_size
    for i in range(0, len(tokens) - len(tokens) % bs, bs):
        chunk = b",".join(str(int(t)).encode() for t in tokens[i:i + bs])
        hx = hashlib.blake2b(prev + b"|" + chunk, digest_size=8).hexdigest()
        out.append(hx)
        prev = bytes.fromhex(hx)
    return out


class BlockPool:
    """Host-side free-list allocator with refcounts over page ids.

    Single-owner by design: the engine's dispatcher thread is the only
    caller (like the slot table it replaces), so there is no lock. The
    sentinel id (== ``num_blocks``) marks unallocated table entries; the
    device scatter drops writes addressed to it (``mode="drop"``).
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks <= 0 or block_size <= 0:
            raise ValueError("num_blocks and block_size must be positive")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # LIFO free list: deterministic allocation order (tests pin it).
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))
        self._ref = [0] * num_blocks

    @property
    def sentinel(self) -> int:
        return self.num_blocks

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - len(self._free)

    def refcount(self, block: int) -> int:
        return self._ref[block]

    def alloc(self, n: int) -> List[int]:
        """``n`` fresh blocks at refcount 1, or KVBlocksExhausted (the
        pool is untouched on failure — all-or-nothing)."""
        if n <= 0:
            return []
        if n > len(self._free):
            raise KVBlocksExhausted(n, len(self._free), self.num_blocks)
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def incref(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            if self._ref[b] <= 0:
                raise KVCacheError(f"incref of free block {b}")
            self._ref[b] += 1

    def decref(self, blocks: Sequence[int]) -> int:
        """Drop one reference per id; ids reaching zero return to the
        free list. Returns how many were actually freed."""
        freed = 0
        for b in blocks:
            if self._ref[b] <= 0:
                raise KVCacheError(f"decref of free block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(b)
                freed += 1
        return freed


@dataclasses.dataclass
class PrefixHit:
    """Result of a trie lookup over one prompt.

    ``blocks``: page ids of the matched FULL leading blocks (read-only,
    not yet increfed — the caller increfs what it adopts).
    ``tokens_matched``: ``len(blocks) * block_size``.
    ``cow_src``/``cow_tokens``: a child block whose first ``cow_tokens``
    tokens match the prompt's next (partial) chunk — the copy-on-write
    donor for mid-block divergence. None/0 when there is none.
    """

    blocks: List[int]
    tokens_matched: int
    cow_src: Optional[int] = None
    cow_tokens: int = 0


class _Node:
    __slots__ = ("key", "block", "children", "stamp", "hash", "hits",
                 "hit_t")

    def __init__(self, key: Tuple[int, ...], block: int, stamp: int,
                 hash_: str = ""):
        self.key = key
        self.block = block
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.stamp = stamp
        # Fleetscope provenance: the chain hash naming this node's exact
        # token prefix, lookup-hit count and last-hit wall time.
        self.hash = hash_
        self.hits = 0
        self.hit_t = time.monotonic()


class PrefixTrie:
    """Block-granular prompt-prefix cache over a :class:`BlockPool`.

    Each node owns one pool reference for its block; eviction (LRU,
    leaves first — an interior node's block is the prefix of its
    children's prompts and must outlive them) drops that reference, so a
    block a live slot still uses survives eviction and only leaves the
    device when its last user retires.
    """

    def __init__(self, pool: BlockPool, max_blocks: int = 0,
                 hit_window: int = 256):
        self.pool = pool
        self.block_size = pool.block_size
        self.max_blocks = max_blocks  # 0 = unbounded (pool pressure evicts)
        self._root = _Node((), -1, 0)
        self._clock = 0
        self._count = 0
        self.hits = 0
        self.lookups = 0
        # Last-N lookup outcomes: the router picks on this WINDOWED rate
        # (lifetime hits/lookups goes inert as uptime grows — a traffic
        # shift at hour 10 barely moves a 10-hour average).
        self._window: collections.deque = collections.deque(
            maxlen=max(1, hit_window))

    @property
    def blocks_held(self) -> int:
        return self._count

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _chunks(self, tokens: Sequence[int]):
        bs = self.block_size
        for i in range(0, len(tokens) - len(tokens) % bs, bs):
            yield tuple(int(t) for t in tokens[i:i + bs])

    def lookup(self, tokens: Sequence[int]) -> PrefixHit:
        """Longest matched full-block prefix plus the best COW donor for
        the next (partial) chunk. Pure apart from the LRU touch."""
        self.lookups += 1
        now = self._tick()
        node = self._root
        blocks: List[int] = []
        for chunk in self._chunks(tokens):
            child = node.children.get(chunk)
            if child is None:
                break
            child.stamp = now
            blocks.append(child.block)
            node = child
        matched = len(blocks) * self.block_size
        # COW donor: any child whose leading tokens equal the remainder.
        rem = [int(t) for t in tokens[matched:matched + self.block_size]]
        cow_src, cow_tokens = None, 0
        if rem and len(rem) < self.block_size:
            for key, child in node.children.items():
                n = 0
                while n < len(rem) and key[n] == rem[n]:
                    n += 1
                if n > cow_tokens:
                    cow_src, cow_tokens = child.block, n
        hit = bool(blocks or cow_tokens)
        if hit:
            self.hits += 1
        self._window.append(1 if hit else 0)
        if blocks:
            # Hot-prefix stats live on the DEEPEST matched node: one
            # lookup = one hit against its longest resident prefix.
            node.hits += 1
            node.hit_t = time.monotonic()
        return PrefixHit(blocks=blocks, tokens_matched=matched,
                         cow_src=cow_src, cow_tokens=cow_tokens)

    def register(self, tokens: Sequence[int],
                 blocks: Sequence[int]) -> int:
        """Publish a prompt's FULL leading blocks (their K/V must already
        be written). ``blocks[i]`` backs tokens ``[i*bs, (i+1)*bs)``.
        Existing nodes win (first writer publishes; a racing identical
        prompt keeps its private copies until retirement). Returns how
        many new nodes were created."""
        now = self._tick()
        node = self._root
        created = 0
        hxs = chunk_hashes(tokens, self.block_size)
        for i, chunk in enumerate(self._chunks(tokens)):
            if i >= len(blocks):
                break
            child = node.children.get(chunk)
            if child is None:
                child = _Node(chunk, int(blocks[i]), now, hash_=hxs[i])
                node.children[chunk] = child
                self.pool.incref([child.block])
                self._count += 1
                created += 1
            child.stamp = now
            node = child
        if self.max_blocks > 0 and self._count > self.max_blocks:
            self.release(self._count - self.max_blocks)
        return created

    def window_hit_rate(self) -> float:
        """Hit rate over the last ``hit_window`` lookups (0.0 when no
        lookup has happened yet)."""
        if not self._window:
            return 0.0
        return sum(self._window) / len(self._window)

    def digest(self, top_k: int = 8, max_hashes: int = 64) -> dict:
        """Compact resident-prefix digest for replica pings (round 22).

        ``hashes``: chain hashes (:func:`chunk_hashes` scheme) of up to
        ``max_hashes`` resident nodes, shallow-first (BFS) so the cap
        drops the DEEPEST chunks first — a truncated digest makes the
        router UNDER-count redundancy, never fabricate it. ``top``: the
        ``top_k`` hottest resident prefixes by lookup hits, each with
        its resident token count and last-hit age. Deterministic for a
        given registration/lookup history: children walk in sorted key
        order, so insertion order never leaks into the digest.
        """
        now = time.monotonic()
        hashes: List[str] = []
        nodes: List[Tuple[_Node, int]] = []
        q = collections.deque([(self._root, 0)])
        while q:
            node, depth = q.popleft()
            for key in sorted(node.children):
                child = node.children[key]
                nodes.append((child, depth + 1))
                if len(hashes) < max_hashes:
                    hashes.append(child.hash)
                q.append((child, depth + 1))
        hot = sorted(nodes,
                     key=lambda nd: (-nd[0].hits, -nd[1], nd[0].hash))
        top = [{"hash": n.hash, "tokens": d * self.block_size,
                "hits": n.hits,
                "age_s": round(max(0.0, now - n.hit_t), 3)}
               for n, d in hot[:top_k] if n.hits > 0]
        return {"block_size": self.block_size, "blocks": self._count,
                "hashes": hashes, "top": top}

    def release(self, n: int) -> int:
        """Evict up to ``n`` LRU leaf nodes, preferring those whose block
        would actually free (refcount 1 = trie-only). Returns the number
        of pool blocks freed.

        One walk of the trie a call: its leaves go on a heap, and a node
        whose last child is evicted joins the heap then, so each eviction
        takes the leaf a fresh walk of the whole trie would pick."""
        if n <= 0:
            return 0
        up: Dict[int, Tuple[_Node, Tuple[int, ...]]] = {}
        heap: list = []
        seq = itertools.count()

        def push(node: _Node):
            # Trie-only leaves first (they free real memory), then LRU;
            # the sequence number keeps the heap off the nodes themselves.
            heapq.heappush(heap, (self.pool.refcount(node.block) > 1,
                                  node.stamp, next(seq), node))

        stack = [self._root]
        while stack:
            node = stack.pop()
            for key, child in node.children.items():
                up[id(child)] = (node, key)
                if child.children:
                    stack.append(child)
                else:
                    push(child)
        freed = 0
        evicted = 0
        while evicted < n and heap:
            child = heapq.heappop(heap)[-1]
            parent, key = up[id(child)]
            del parent.children[key]
            freed += self.pool.decref([child.block])
            self._count -= 1
            evicted += 1
            if parent is not self._root and not parent.children:
                push(parent)
        return freed

    def clear(self) -> int:
        return self.release(self._count)


# -- cache-pytree helpers ----------------------------------------------------
#
# The flax cache collection nests one dict per attention layer:
#   {"layer_i": {"attn": {"pages_k", "pages_v", "page_tbl",
#                         "cache_index"}}}
# Engines keep the pool leaves (pages_k/v) as donated device state and
# re-inject a host-built table window + index per call. Pure-tree code so
# it runs inside jit.

_TABLE_KEYS = ("page_tbl", "cache_index")


def with_tables(pages_tree: dict, tbl, ci) -> dict:
    """Rebuild a full cache tree from pool leaves + one shared table
    window + index (the same arrays serve every layer)."""
    if isinstance(pages_tree, dict):
        if "pages_k" in pages_tree:
            out = dict(pages_tree)
            out["page_tbl"] = tbl
            out["cache_index"] = ci
            return out
        return {k: with_tables(v, tbl, ci) for k, v in pages_tree.items()}
    return pages_tree


def split_cache(cache: dict):
    """Full cache tree -> (pool-leaves-only tree, cache_index). The
    per-layer table/index copies are identical by construction; the first
    index found is returned, tables are dropped (the host owns them)."""
    ci_box = [None]

    def strip(node):
        if isinstance(node, dict):
            if "pages_k" in node:
                if ci_box[0] is None:
                    ci_box[0] = node.get("cache_index")
                return {k: v for k, v in node.items()
                        if k not in _TABLE_KEYS}
            return {k: strip(v) for k, v in node.items()}
        return node

    pages = strip(cache)
    return pages, ci_box[0]


# A recurrent layer's state does not grow a token a step and lives in no
# page: its cache leaves (``models/transformer.slot_leaves`` names them)
# are SLOT leaves, ``[max_slots, ...]``, one row per slot of the engine.
# They ride in the same tree as the pool leaves; a program gathers the
# rows of the slots it serves, runs the module on that compact batch and
# scatters the rows back. A sentinel slot id (== max_slots) gathers
# clipped and scatters dropped, as a sentinel page does.

def _map_slot_leaves(fn, names: tuple, tree, *rest):
    if isinstance(tree, dict):
        return {k: (fn(v, *(r[k] for r in rest)) if k in names
                    else _map_slot_leaves(fn, names, v,
                                          *(r[k] for r in rest)))
                for k, v in tree.items()}
    return tree


def take_slots(tree: dict, names: tuple, ids, fresh=None):
    """The state each row starts from: (``tree`` with each slot leaf cut
    to the rows ``ids`` [nb], ``link`` [nb] bool). Rows where ``fresh``
    [nb] holds start from zero (a sequence's first tokens). A row that is
    not fresh and names the slot of the row before it LINKS: it holds
    that row's next tokens and starts from the state that row ends with,
    not from its slot's (``MambaMixer``). Pool leaves pass through;
    without slot leaves the tree is ``tree`` and ``link`` None."""
    import jax.numpy as jnp

    if not names:
        return tree, None

    def take(leaf):
        rows = leaf.at[ids].get(mode="clip")
        if fresh is None:
            return rows
        return jnp.where(
            fresh.reshape((-1,) + (1,) * (rows.ndim - 1)), 0, rows)

    link = ids == jnp.roll(ids, 1).at[0].set(-1)
    if fresh is not None:
        link = link & ~fresh
    return _map_slot_leaves(take, names, tree), link


def put_slots(resident: dict, compact: dict, names: tuple, ids) -> dict:
    """``compact`` (a program's tree after the module ran: its pool leaves
    are the pool, its slot leaves hold ``ids``' rows) with each slot leaf
    scattered back into ``resident``'s ``[max_slots, ...]`` leaf."""
    if not names:
        return compact
    return _map_slot_leaves(
        lambda rows, big: big.at[ids].set(rows.astype(big.dtype),
                                          mode="drop"),
        names, compact, resident)


def slot_bytes(tree, names: tuple) -> int:
    """Bytes ONE slot's rows of the slot leaves take (shapes suffice)."""
    if not isinstance(tree, dict):
        return 0
    return sum(math.prod(v.shape[1:]) * v.dtype.itemsize if k in names
               else slot_bytes(v, names) for k, v in tree.items())


def paged_module(module, block_size: int, num_blocks: int):
    """A serving twin of ``module`` whose attention uses the paged cache
    (same params — the kv fields only reroute the cache variables)."""
    cfg = dataclasses.replace(module.cfg, kv_page_size=block_size,
                              kv_pages=num_blocks)
    return type(module)(cfg)


def sequential_table(batch: int, max_pages: int, num_blocks: int):
    """Row-major dense block table for callers that don't share pages
    (``generate`` over a paged cache): row b owns pages
    [b*max_pages, (b+1)*max_pages). Requires num_blocks >= B*max_pages."""
    import numpy as np

    if batch * max_pages > num_blocks:
        raise KVBlocksExhausted(batch * max_pages, num_blocks, num_blocks)
    return np.arange(batch * max_pages, dtype=np.int32).reshape(
        batch, max_pages)
