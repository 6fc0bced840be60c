"""Paged KV cache — block-pooled pages so memory scales with live tokens.

A serving process admits and retires sequences continuously; a
contiguous per-sequence KV buffer sized for the maximum context would
waste ``max_context - live`` slots per sequence and make admission a
memory-compaction problem.  The paged design (vLLM's PagedAttention,
PAPERS.md "LLM Inference Acceleration via Efficient Operation Fusion"
motivates the fused read side) splits the cache into fixed-size
**pages** drawn from one shared pool:

- **device side** — one pool per layer, stacked: ``k``/``v`` arrays of
  shape ``(L, P, H/G, page, W)`` — heads OUTSIDE the page dim (the
  layout :func:`apex_tpu.ops.paged_decode_attention` contracts with no
  transposes), ``G`` heads side by side in one 128-lane row
  (:func:`~apex_tpu.ops.paged_attention.heads_per_row`: ``G = 2`` at
  ``head_dim`` 64, 1 from 128 up), ``W = lane_width(D*G)``: a row that
  does not fill its tiles (heads that do not pair up, or of 80 or 96
  lanes) is zero-padded to them.  A lane-dense minor dimension is what
  lets XLA:TPU keep the pool in plain row-major layout — the one layout
  the kernel, the appends and the prompt writes all agree on, so no
  serving program ever relays the pool (docs/serving.md "The KV pool").
  With ``kv_wire="int8"`` the pools hold blockwise int8 codes plus f32
  scale planes ``(L, P, 1, page, lane_width(H))`` — one scale per
  (head, token) at ``block = head_dim``, a token a row and a head a
  lane, whole 128-lane tiles like the pages so the decode kernel copies
  them the same way — the exact ``parallel/comm.py`` codec
  (:func:`~apex_tpu.parallel.comm.quantize_blocks`), so the KV wire
  format is the same code the gradient wire uses.
- **host side** — :class:`PagePool`, a free-list allocator.  Page 0 is
  the reserved **null page**: page-table entries beyond a sequence's
  live count point at it, padded prefill tails scatter into it, and
  idle decode slots append into it — it is write-only garbage that the
  ``lengths`` masking guarantees is never read.

There is no defragmentation pass and none is needed: pages are
fixed-size and fully owned by one sequence, so freeing a sequence
returns its pages to the free list with zero compaction — occupancy is
exactly ``live_pages / usable_pages`` at all times.

**The cache set of a stack whose layers differ in kind**
(:func:`init_hybrid_cache`, docs/serving.md "Layer kinds and the cache
set") is DECLARED: each mixer kind says once what it keeps
(:class:`CacheKind`: per token or per slot, over how many layers, a row's
shape, its dtype — :func:`hybrid_cache_kinds`), and the allocation, the
scheduler's gauges and the engine's ``memory-pool-copy`` intent read that
declaration.  Beside — or instead of — the K/V page kind above (which a
grouped-query branch declares at its ``num_kv_heads``) it holds, all of it
the layer loop's carry and donated like the pool:

- a **latent page kind** — one row a token for every latent-attention
  (MLA) layer, ``(L_mla, P, 1, page, W)``: the normalised latent and the
  rotated shared key side by side, zero-padded to whole 128-lane tiles.
  Pages, page tables, the null page and :class:`PagePool` are the ones
  above; every query head reads the same row.
- a **per-slot recurrent slab** — for every linear-attention (KDA) layer
  the f32 state ``(L_kda, B, H, d_v, d_k)`` of each decode slot and the
  short convolution's last inputs ``(L_kda, B, taps - 1, C)``.  It is not
  positional: it cannot be paged, shared by a prefix, forked or rolled
  back by page.  A slot's rows are written whole by the prefill that
  admits a sequence into it (so a reused slot starts from that sequence's
  own state, whatever it held) and advanced in place by every decode step.
  A state-space (Mamba-2) branch keeps the same two things under its own
  names: ``"ssm"`` ``(L_ssm, B, H, P, N)`` f32 and ``"ssm_conv"``
  ``(L_ssm, B, taps - 1, C)``.

The device-side write helpers here are pure functions meant to be
called INSIDE the engine's jitted step programs, on the WHOLE pool with
a layer index: the programs carry the pool through their layer loop and
every write is a scatter of whole pages at ``[layer, page_ids]``, the
one form XLA:TPU performs in place on the donated buffer (the engine's
``memory-pool-copy`` lint proves at build that no program holds a
layer-sized temporary).
"""

from __future__ import annotations

import dataclasses
import hashlib
from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from apex_tpu.ops.mla import latent_row_width
from apex_tpu.ops.paged_attention import (
    heads_per_row,
    lane_width,
    pad_lanes,
)
from apex_tpu.parallel import comm

__all__ = [
    "NULL_PAGE",
    "PagePool",
    "PrefixCache",
    "prefix_keys",
    "init_kv_pages",
    "encode_kv",
    "pack_prompt_pages",
    "write_pages",
    "append_rows",
    "write_prompt_kv",
    "append_token_kv",
    "CacheKind",
    "hybrid_cache_kinds",
    "init_hybrid_cache",
    "write_prompt_latent",
    "append_token_latent",
    "write_slot_state",
]

#: page 0 — never allocated; the write-only garbage target for padded
#: tails and idle slots
NULL_PAGE = 0


class PagePool:
    """Host-side free-list allocator over ``num_pages`` device pages.

    Page 0 (:data:`NULL_PAGE`) is reserved, so ``num_pages - 1`` pages
    are usable.  ``alloc`` is all-or-nothing: a request that cannot get
    every page it asked for gets none (no partial admissions to later
    roll back — the scheduler's shedding logic stays trivial).

    Pages are **refcounted**: ``alloc`` hands a page out at refcount 1,
    :meth:`share` adds a reference (a prefix-cache borrow or the
    cache's own hold on a committed run), and :meth:`free` RELEASES one
    reference — the page returns to the free list only when the last
    holder lets go.  Every existing free path (retire, shed, reroute)
    is therefore automatically safe for shared pages: a retried request
    that borrowed cached pages decrements, it never yanks pages a
    co-rider still reads.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.num_pages = num_pages
        self.page_size = page_size
        # LIFO free list: recently freed pages are re-used first (their
        # content is dead by construction, and re-use keeps the touched
        # working set small)
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        #: allocated page -> reference count (absent = free)
        self._refs: Dict[int, int] = {}
        #: allocated page -> namespace tag (absent = free).  The
        #: default namespace is ``"kv"`` (target-model KV); a
        #: speculative engine allocates its draft-model pages under
        #: ``"draft"`` so :meth:`leak_check` can prove draft pages
        #: never reach the prefix cache (a draft page's content is a
        #: DIFFERENT model's KV — sharing it into the target cache
        #: would corrupt every borrower bit-exactly enough to be
        #: missed by shape checks).
        self._ns: Dict[int, str] = {}

    @property
    def usable(self) -> int:
        return self.num_pages - 1

    @property
    def available(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.usable - self.available

    def occupancy(self) -> float:
        """Live fraction of the usable pool (0..1)."""
        return self.in_use / self.usable

    def pages_for(self, tokens: int) -> int:
        """Pages needed to hold ``tokens`` KV positions."""
        return -(-max(tokens, 0) // self.page_size)

    def alloc(self, n: int, ns: str = "kv") -> Optional[List[int]]:
        """``n`` pages, or None when the pool cannot cover all of them
        (all-or-nothing; never hands out :data:`NULL_PAGE`).  ``ns``
        tags the pages with a namespace (``"kv"`` target KV —
        the default — or ``"draft"`` for speculative-draft KV); the
        tag rides the page until its last reference is freed."""
        if n < 0:
            raise ValueError("cannot allocate a negative page count")
        if n > len(self._free):
            return None
        taken = [self._free.pop() for _ in range(n)]
        for p in taken:
            self._refs[p] = 1
            self._ns[p] = ns
        return taken

    def namespace(self, page: int) -> Optional[str]:
        """The namespace tag of an allocated page (None = free)."""
        return self._ns.get(page)

    def share(self, pages: List[int]) -> None:
        """Add one reference per page (a prefix-cache borrow, or the
        cache's own hold on a freshly committed run).  Sharing a page
        that is not allocated is a bug loud enough to raise."""
        for p in pages:
            if p not in self._refs:
                raise ValueError(f"cannot share unallocated page {p}")
        for p in pages:
            self._refs[p] += 1

    def refcount(self, page: int) -> int:
        """Current reference count of ``page`` (0 = free)."""
        return self._refs.get(page, 0)

    def free(self, pages: List[int]) -> None:
        """Release one reference per page; a page returns to the free
        list only at refcount 0 (shared pages survive their
        co-holders' frees)."""
        for p in pages:
            if not 0 < p < self.num_pages:
                raise ValueError(f"page {p} is not an allocatable page id")
            if p not in self._refs:
                raise ValueError(f"double free of page {p}")
        for p in pages:
            r = self._refs[p] - 1
            if r:
                self._refs[p] = r
            else:
                del self._refs[p]
                self._ns.pop(p, None)
                self._free.append(p)

    def leak_check(self, owned, cached=()) -> None:
        """Assert the pool's accounting is EXACT against the live
        ownership ledger: every allocated page's refcount equals the
        number of live holders claiming it, and every claimed page is
        allocated.

        ``owned`` is an iterable of per-request page lists (the
        scheduler's slots + retrying queue entries); ``cached`` is the
        prefix cache's committed-run pages (each entry holds exactly
        one reference of its own).  Raises ``ValueError`` naming the
        leaked (refcounted above the ownership ledger — e.g. allocated
        but unowned), foreign (claimed but not allocated), or
        double-owned (claimed by more holders than references — a
        duplicate claim that never went through :meth:`share`) pages —
        the invariant the serving chaos drill re-proves after every
        injected fault (docs/serving.md "Failure semantics")."""
        want: Counter = Counter()
        for pages in owned:
            want.update(pages)
        want.update(cached)
        problems = []
        over = sorted(p for p, c in want.items()
                      if c > self._refs.get(p, 0) and p in self._refs)
        if over:
            problems.append(f"pages owned by more than one request "
                            f"without a shared reference: {over}")
        leaked = sorted(p for p, r in self._refs.items() if r > want[p])
        foreign = sorted(set(want) - set(self._refs))
        if leaked:
            problems.append(
                f"leaked pages (allocated references owned by no live "
                f"request or cache entry): {leaked}"
            )
        if foreign:
            problems.append(
                f"foreign pages (owned but not allocated): "
                f"{foreign}"
            )
        draft_cached = sorted(
            p for p in cached if self._ns.get(p, "kv") != "kv"
        )
        if draft_cached:
            problems.append(
                f"draft-namespace pages shared into the prefix cache: "
                f"{draft_cached}"
            )
        if problems:
            raise ValueError(
                "PagePool leak check failed: " + "; ".join(problems)
            )


# ---------------------------------------------------------------------------
# cross-request prefix cache: content hash -> committed KV page run
# ---------------------------------------------------------------------------


def prefix_keys(prompt, page_size: int) -> List[Tuple[bytes, int]]:
    """Chained page-granularity content keys for a prompt:
    ``key_i = H(key_{i-1} || tokens[i*page:(i+1)*page])`` — a page's key
    commits to EVERY token before it, so two prompts share a key iff
    they share the whole prefix up to that page.  The final partial
    page (if any) gets a key too: only a whole-prompt hit can reuse a
    partially-filled tail page, because its content embeds the exact
    partial token run.  Returns ``[(key, tokens_through_here), ...]``.
    """
    out: List[Tuple[bytes, int]] = []
    key = b"apex-prefix-v1"
    for start in range(0, len(prompt), page_size):
        block = np.asarray(prompt[start:start + page_size], np.int32)
        key = hashlib.blake2b(
            key + block.tobytes(), digest_size=16
        ).digest()
        out.append((key, start + len(block)))
    return out


class _CacheEntry:
    __slots__ = ("key", "page", "tokens", "parent", "children", "tick")

    def __init__(self, key, page, tokens, parent, tick):
        self.key = key
        self.page = page          # the committed device page id
        self.tokens = tokens      # prompt tokens through this page
        self.parent = parent      # previous key in the chain (or None)
        self.children = 0         # cached entries chaining through us
        self.tick = tick          # LRU clock

    def __repr__(self):
        return (f"_CacheEntry(page={self.page}, tokens={self.tokens}, "
                f"children={self.children}, tick={self.tick})")


class PrefixCache:
    """Content-addressed map from chained prompt-prefix hashes to
    committed KV page runs in one :class:`PagePool`.

    - **commit** — after a prompt's prefill completes (and before its
      first decode append), each of its pages is published under its
      chain key with one cache-owned :meth:`PagePool.share` reference,
      so the run outlives the committing request.
    - **match** — an admitted prompt walks its key chain for the
      longest cached run; :meth:`borrow` adds one reference per page
      for the borrower (released by the borrower's ordinary
      ``pool.free`` on retire/shed/retry — refcounts make every
      existing free path shared-safe).
    - **copy-on-write** — fully-filled shared pages are never written
      again (decode appends land past them), so they are shared
      forever; a shared partially-filled TAIL page is forked by the
      scheduler before its first append (``refcount > 1`` at the
      append page is the trigger).
    - **eviction** — :meth:`evict` frees least-recently-used entries
      with NO borrowers (pool refcount 1 = the cache's own reference),
      leaf-first along the chain so a parent with a cached child is
      never evicted from under it.

    The cache is host-side bookkeeping only; page content lives in the
    engine's donated KV arrays and is never touched here.
    """

    def __init__(self, pool: PagePool):
        self.pool = pool
        self.entries: Dict[bytes, _CacheEntry] = {}
        self._tick = 0
        # cumulative ledger (the scheduler mirrors these to counters)
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0
        self.evictions = 0
        self.commits = 0

    def __len__(self) -> int:
        return len(self.entries)

    def cached_pages(self) -> List[int]:
        """Pages the cache holds a reference on (the ``cached=`` arm of
        :meth:`PagePool.leak_check`)."""
        return [e.page for e in self.entries.values()]

    # -- lookup ------------------------------------------------------------
    def _walk(self, prompt) -> List[_CacheEntry]:
        """Longest cached run from page 0: consecutive full-page
        entries, plus the partial tail entry only when everything
        before it matched (a tail key embeds the whole prompt)."""
        run: List[_CacheEntry] = []
        for key, _end in prefix_keys(prompt, self.pool.page_size):
            e = self.entries.get(key)
            if e is None:
                break
            run.append(e)
        return run

    def peek_tokens(self, prompt) -> int:
        """Match length in tokens WITHOUT touching LRU state or
        borrowing — the router's affinity probe."""
        run = self._walk(prompt)
        return run[-1].tokens if run else 0

    def match(self, prompt) -> Tuple[List[int], int]:
        """``(pages, tokens)`` of the longest cached prefix run
        (LRU-touched).  The pages are NOT yet borrowed — call
        :meth:`borrow` once the request's remaining allocation
        succeeded (all-or-nothing admission must not hold references
        it may have to unwind)."""
        run = self._walk(prompt)
        self._tick += 1
        if not run:
            self.misses += 1
            return [], 0
        for e in run:
            e.tick = self._tick
        self.hits += 1
        self.hit_tokens += run[-1].tokens
        return [e.page for e in run], run[-1].tokens

    def borrow(self, pages: List[int]) -> None:
        """One reference per matched page for the borrowing request —
        from here on the borrower's normal ``pool.free`` is the
        release."""
        self.pool.share(pages)

    # -- publication -------------------------------------------------------
    def commit(self, prompt, pages: List[int]) -> int:
        """Publish a prefilled prompt's pages under their chain keys
        (one cache-owned reference each); keys already cached keep
        their incumbent page (two racing cold prefills of the same
        prompt do not double-publish).  The chain stops at the first
        key whose incumbent differs from ours — a child entry must
        chain through OUR parent pages or a later match would stitch
        pages from different runs.  Returns the number of new
        entries."""
        self._tick += 1
        added = 0
        parent = None
        for (key, end), page in zip(
            prefix_keys(prompt, self.pool.page_size), pages
        ):
            e = self.entries.get(key)
            if e is not None:
                e.tick = self._tick
                if e.page != page:
                    # an equivalent run is already published; our copy
                    # of the suffix would chain through pages the
                    # cached parent run does not reference
                    break
                parent = key
                continue
            self.pool.share([page])
            self.entries[key] = _CacheEntry(
                key, page, end, parent, self._tick
            )
            if parent is not None:
                self.entries[parent].children += 1
            parent = key
            added += 1
        if added:
            self.commits += 1
        return added

    # -- eviction ----------------------------------------------------------
    def _evictable(self) -> List[_CacheEntry]:
        """Leaf entries (no cached children) with no live borrowers
        (pool refcount 1 = only the cache's own reference), oldest
        first."""
        return sorted(
            (e for e in self.entries.values()
             if e.children == 0 and self.pool.refcount(e.page) == 1),
            key=lambda e: (e.tick, e.page),
        )

    def _drop(self, e: _CacheEntry) -> None:
        del self.entries[e.key]
        if e.parent is not None and e.parent in self.entries:
            self.entries[e.parent].children -= 1
        self.pool.free([e.page])
        self.evictions += 1

    def evict(self, need: Optional[int] = None) -> int:
        """Free least-recently-used borrower-free cached pages until
        ``need`` pages came back to the pool (None = everything
        evictable).  A parent whose last cached child is evicted
        becomes a leaf and is considered in the same sweep.  Entries
        with live borrowers are NEVER evicted — a borrowed stream's
        pages stay resident by construction.  Returns pages freed."""
        freed = 0
        while need is None or freed < need:
            cands = self._evictable()
            if not cands:
                break
            take = cands if need is None else cands[: need - freed]
            for e in take:
                self._drop(e)
                freed += 1
                if need is not None and freed >= need:
                    break
        return freed

    def flush(self) -> int:
        """Teardown (drain seal / replica evacuation): release EVERY
        cache-owned reference unconditionally — entries with live
        borrowers only drop the cache's hold, the borrowers' own
        references keep those pages allocated.  Returns the entry
        count released."""
        n = len(self.entries)
        for e in list(self.entries.values()):
            self.pool.free([e.page])
        self.entries.clear()
        self.evictions += n
        return n


# ---------------------------------------------------------------------------
# device-side pure helpers (called inside the engine's jitted steps)
# ---------------------------------------------------------------------------


def init_kv_pages(
    num_layers: int,
    num_pages: int,
    num_heads: int,
    page_size: int,
    head_dim: int,
    *,
    dtype=jnp.bfloat16,
    kv_wire: str = "f32",
) -> dict:
    """Fresh zeroed pool arrays: ``{"k", "v"}`` of ``(L, P, H/G, page,
    W)`` with ``G = heads_per_row(H, D)`` and ``W = lane_width(D*G)`` —
    whole 128-lane tiles, the lanes past ``D*G`` zero and never written
    (heads that do not pair up, or of 80 or 96 lanes: the decode kernel
    copies pages out of HBM by whole tiles) — plus ``{"k_scale", "v_scale"}``
    ``(L, P, 1, page, lane_width(H))`` f32 planes under ``kv_wire="int8"``
    (a token a row, a head a lane; codes then carry dtype int8)."""
    if kv_wire not in ("f32", "int8"):
        raise ValueError(f"kv_wire must be 'f32' or 'int8', got {kv_wire!r}")
    g = heads_per_row(num_heads, head_dim)
    rows = (num_layers, num_pages, num_heads // g, page_size)
    store = jnp.int8 if kv_wire == "int8" else dtype
    w = lane_width(head_dim * g)
    cache = {
        "k": jnp.zeros(rows + (w,), store),
        "v": jnp.zeros(rows + (w,), store),
    }
    if kv_wire == "int8":
        # two DISTINCT buffers: the engine donates the whole cache
        # tree, and donating one shared buffer twice is a runtime error
        scales = (num_layers, num_pages, 1, page_size, lane_width(num_heads))
        cache["k_scale"] = jnp.ones(scales, jnp.float32)
        cache["v_scale"] = jnp.ones(scales, jnp.float32)
    return cache


def encode_kv(x):
    """Blockwise int8 codes + scales for KV rows ``(..., D)`` — the
    ``parallel/comm.py`` codec at ``block = D`` (one f32 scale per
    (head, token) row; an all-zero row gets scale 1.0, so the null page
    stays NaN-free)."""
    d = x.shape[-1]
    codes, scale = comm.quantize_blocks(x.astype(jnp.float32), block=d)
    return codes, scale[..., 0]


def pack_prompt_pages(rows, page_size: int):
    """``(S, R, W)`` per-position rows -> ``(NP, R, page, W)`` page
    blocks (``S`` must be a page multiple — prefill buckets are)."""
    s, r, w = rows.shape
    if s % page_size:
        raise ValueError(f"prompt length {s} is not a page multiple")
    return jnp.transpose(
        rows.reshape(s // page_size, page_size, r, w), (0, 2, 1, 3)
    )


def write_pages(pool, layer, page_ids, blocks):
    """Scatter whole page blocks ``(NP, R, page, W)`` into layer
    ``layer`` of ``pool`` ``(L, P, R, page, W)`` at ``page_ids``
    ``(NP,)``.  Entries pointing at the null page dump a padded tail
    there (never read back)."""
    return pool.at[layer, page_ids].set(blocks.astype(pool.dtype))


def append_rows(pool, layer, page_ids, slots, rows):
    """Put one row ``(B, R, W)`` per sequence into layer ``layer`` of
    ``pool`` at ``(page_ids[b], slots[b])`` by read-modify-write of the
    batch's tail pages: gather them, select the new row in, scatter the
    whole pages back.  A per-row scatter ``pool.at[l, page, :, slot]``
    makes XLA:TPU relay the whole pool around it.  Idle slots all
    target the null page (duplicate indices — any winner is fine, it is
    never read); no two live sequences share a tail page (the
    scheduler's copy-on-write fork)."""
    pages = pool[layer, page_ids]  # (B, R, page, W)
    at_slot = (
        jnp.arange(pool.shape[3], dtype=slots.dtype)[None, :]
        == slots[:, None]
    )[:, None, :, None]
    pages = jnp.where(at_slot, rows.astype(pool.dtype)[:, :, None, :], pages)
    return write_pages(pool, layer, page_ids, pages)


def _planes(kv, k, v):
    """K/V rows ``(..., H, D)`` as the pool's planes ``{name: (..., R,
    W)}``: lane rows of ``G`` heads (a free reshape) and, when ``kv``
    carries scale planes, int8 codes and their scales a token a row —
    each zero-padded to its plane's whole tiles."""
    g = k.shape[-2] // kv["k"].shape[2]
    planes = {"k": k, "v": v}
    if "k_scale" in kv:
        planes["k"], k_scale = encode_kv(k)
        planes["v"], v_scale = encode_kv(v)
    planes = {
        name: x.reshape(x.shape[:-2] + (x.shape[-2] // g, g * x.shape[-1]))
        for name, x in planes.items()
    }
    if "k_scale" in kv:
        planes["k_scale"] = k_scale[..., None, :]
        planes["v_scale"] = v_scale[..., None, :]
    return {
        name: pad_lanes(x, kv[name].shape[-1]) for name, x in planes.items()
    }


def write_prompt_kv(kv, layer, page_ids, k, v):
    """Write one layer's prompt K/V ``(S, H, D)`` (``S`` a page
    multiple) into pages ``page_ids`` ``(S/page,)`` of every plane of
    the pool dict ``kv``."""
    page_size = kv["k"].shape[3]
    return dict(kv, **{
        name: write_pages(
            kv[name], layer, page_ids, pack_prompt_pages(rows, page_size)
        )
        for name, rows in _planes(kv, k, v).items()
    })


def append_token_kv(kv, layer, page_ids, slots, k, v):
    """Append one token's K/V ``(B, H, D)`` per sequence to layer
    ``layer`` of every plane of the pool dict ``kv`` at
    ``(page_ids[b], slots[b])`` — the per-layer decode append."""
    return dict(kv, **{
        name: append_rows(kv[name], layer, page_ids, slots, rows)
        for name, rows in _planes(kv, k, v).items()
    })


# ---------------------------------------------------------------------------
# the cache set of a hybrid stack: latent pages + per-slot recurrent slab
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """One entry of a hybrid stack's cache set, declared by the mixer kind
    that keeps it.  ``per`` says what a row belongs to: ``"token"`` — a
    page kind, ``(layers, num_pages, *shape)``, ``shape`` one page's
    ``(rows, page, lanes)``, addressed through the page tables and
    :class:`PagePool` — or ``"slot"`` — ``(layers, max_batch, *shape)``,
    one row a decode slot, written whole by the prefill that admits a
    sequence and advanced in place by every decode step.  ``in_place``: the
    entry is under the ``memory-pool-copy`` rule (no program may hold a
    layer-sized temporary of it) and counts as the model's state in
    ``serve/state/bytes``; a convolution tail is not (a step rewrites a
    layer of it whole, and it is small)."""

    name: str
    per: str
    layers: int
    shape: Tuple[int, ...]
    dtype: Any
    in_place: bool = True

    def full_shape(self, num_pages: int, max_batch: int) -> Tuple[int, ...]:
        rows = num_pages if self.per == "token" else max_batch
        return (self.layers, rows) + tuple(self.shape)

    @property
    def row_bytes(self) -> int:
        """Bytes of one page, or of one slot's share, over all the layers."""
        return self.layers * int(np.prod(self.shape)) * jnp.dtype(
            self.dtype).itemsize


def _kda_kinds(cfg, n, page_size):
    h, d = cfg.num_heads, cfg.head_dim
    return (
        CacheKind("state", "slot", n, (h, d, d), jnp.float32),
        CacheKind("conv", "slot", n, (cfg.conv_kernel - 1, 3 * h * d),
                  cfg.dtype, in_place=False),
    )


def _mla_kinds(cfg, n, page_size):
    w = latent_row_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
    return (CacheKind("latent", "token", n, (1, page_size, w), cfg.dtype),)


def _ssm_gqa_kinds(cfg, n, page_size):
    # K and V as the GPT pool lays them (init_kv_pages), at the KV heads
    kv, d = cfg.kv_heads, cfg.head_dim
    g = heads_per_row(kv, d)
    page = (kv // g, page_size, lane_width(d * g))
    return (
        CacheKind("k", "token", n, page, cfg.dtype),
        CacheKind("v", "token", n, page, cfg.dtype),
        CacheKind("ssm", "slot", n,
                  (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                  jnp.float32),
        CacheKind("ssm_conv", "slot", n,
                  (cfg.conv_kernel - 1, cfg.ssm_conv_width), cfg.dtype,
                  in_place=False),
    )


#: what each mixer kind keeps: ``(cfg, layers of the kind, page size) ->
#: CacheKinds``.  A new mixer kind declares its state HERE, once.
_KINDS_OF = {"kda": _kda_kinds, "mla": _mla_kinds, "ssm_gqa": _ssm_gqa_kinds}


def hybrid_cache_kinds(cfg, page_size: int) -> Tuple[CacheKind, ...]:
    """The cache set a :class:`~apex_tpu.models.hybrid.HybridConfig` asks
    for, as declarations: each mixer kind present says what it keeps, over
    its own layers (a layer's index inside an entry is its rank among the
    layers of its kind).  A kind with no layer declares nothing."""
    out = []
    for mixer, declare in _KINDS_OF.items():
        n = len(cfg.layers_of(mixer))
        if n:
            out.extend(declare(cfg, n, page_size))
    return tuple(out)


def init_hybrid_cache(cfg, num_pages: int, page_size: int,
                      max_batch: int) -> dict:
    """Fresh zeroed cache set for a :class:`~apex_tpu.models.hybrid.
    HybridConfig`, one array a declared :class:`CacheKind`
    (:func:`hybrid_cache_kinds`): e.g. ``"latent"`` ``(L_mla, P, 1, page,
    W)`` in the compute dtype, ``"state"`` ``(L_kda, B, H, d_v, d_k)`` f32
    and ``"conv"`` ``(L_kda, B, taps - 1, 3 H d)`` in the compute dtype."""
    return {
        kind.name: jnp.zeros(kind.full_shape(num_pages, max_batch), kind.dtype)
        for kind in hybrid_cache_kinds(cfg, page_size)
    }


def write_prompt_latent(cache, layer, page_ids, rows):
    """One MLA layer's prompt rows ``(S, W)`` into pages ``page_ids``."""
    page_size = cache["latent"].shape[3]
    return dict(cache, latent=write_pages(
        cache["latent"], layer, page_ids,
        pack_prompt_pages(rows[:, None, :], page_size),
    ))


def append_token_latent(cache, layer, page_ids, slots, rows):
    """One token's latent row ``(B, W)`` per sequence at ``(page_ids[b],
    slots[b])`` of MLA layer ``layer``."""
    return dict(cache, latent=append_rows(
        cache["latent"], layer, page_ids, slots, rows[:, None, :]
    ))


def write_slot_state(cache, layer, slot, state, conv_tail, *,
                     names=("state", "conv")):
    """A sequence's whole recurrent state into decode slot ``slot`` of
    layer ``layer`` of its kind: ``state`` ``(H, d_v, d_k)``, ``conv_tail``
    ``(taps - 1, C)``, under the entries ``names`` (KDA's by default; the
    state-space branch's are ``("ssm", "ssm_conv")``).  What the slot held
    before is gone."""
    slab, tail = names
    return dict(cache, **{
        slab: cache[slab].at[layer, slot].set(state),
        tail: cache[tail].at[layer, slot].set(
            conv_tail.astype(cache[tail].dtype)
        ),
    })
