"""Cache layouts + block/paged KV-cache management for serving.

The decode surface is ONE protocol: a :class:`CacheLayout` owns the
physical cache pytree and answers, per layer,

  * **init**     — build the cache leaves (slot rows or page pools);
  * **write**    — where a request's prefilled KV/state lands
    (``insert`` / ``insert_prefill``) and how decode steps address it
    (``tables`` for paged, per-row indices for slots);
  * **read**     — the kwargs a decode step needs (``step_kwargs``);
  * **snapshot / restore** — copy-out / masked copy-back of the
    RECURRENT leaves (mamba / xLSTM state), the rollback primitive
    speculative decoding is built on.  Attention KV needs no rollback:
    stale positions past a row's length are causally masked and
    overwritten on the next write.

Two implementations, both driven through
:class:`repro.serve.session.DecodeSession`:

``SlotLayout``
    Dense rows: ``num_slots x max_len`` attention KV + per-slot
    recurrent state (the PR-2 layout, kept as the ``layout="dense"``
    baseline the fig14 benchmark measures the paged path against).

``PagedLayout``
    Per attention layer ONE ``(num_pages + 1, block_size, n_kv_heads,
    head_dim)`` pool (``repro.models.lm.init_cache(..., pages=...)``;
    the +1 is the null page), plus the host-side block tables the
    gather-decode kernel reads.  A request's pages can live anywhere in
    the pool — there is no per-slot ``max_len`` row, so a single
    request may use the entire pool.  Recurrent-layer state (O(1) per
    request) stays in dense per-slot rows.

    **Prefix sharing (copy-on-admit):** after a request prefills, its
    fully-filled prompt pages are registered in a prefix cache keyed by
    the token chain they hold; a later request whose prompt starts with
    the same pages maps them read-only into its own table (refcount++)
    and prefills only the suffix.  Shared pages are immutable by
    construction — decode appends strictly after the prompt and the
    partially-filled tail page is never shared — so no copy is ever
    needed.  With ``pin_prefix=True`` registered prompt pages are
    additionally PINNED: they survive idle periods (no live holder) in
    an eviction-priority tier and are reclaimed oldest-first only under
    allocation pressure.

``BlockManager``
    Page accounting in units of ``block_size`` tokens over a fixed page
    pool — vLLM-style bookkeeping with two layers of truth:

    * **reservations** — admission is by token budget: a request is
      admitted only when its full reservation (prompt + max new tokens)
      fits under the pool size minus everything already committed, so
      the scheduler never has to preempt mid-stream;
    * **physical pages** — materialized lazily (``ensure``): prompt
      pages as prefill reaches them, decode pages when generation
      crosses a page boundary.  A request that stops early (EOS) never
      claims the tail of its reservation, and the high-water mark
      measures pages actually touched.

    Pages are **refcounted** so prefix sharing can map one physical
    page into several requests' tables; a page returns to the free list
    when its last holder releases it — unless it is pinned, in which
    case it idles in the reclaim tier.
"""
from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm


def _chain_key(prev: Optional[bytes], chunk: np.ndarray) -> bytes:
    """Prefix-cache key of a fully-filled page: a 16-byte BLAKE2b digest
    of the previous page's key and this page's token ids, so equal keys
    mean equal prefixes (a false match needs a 128-bit collision) and a
    key costs one page of hashing, whatever the prefix's length (a
    nested tuple of every earlier page hashed and compared in time
    growing with the prefix: quadratic over a 32k-token prompt)."""
    h = hashlib.blake2b(prev or b"", digest_size=16)
    h.update(np.asarray(chunk, np.int64).tobytes())
    return h.digest()


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Pages needed to hold `n_tokens` cache entries."""
    return max(1, -(-int(n_tokens) // int(block_size)))


@dataclass
class BlockManager:
    """Token-budget page accounting over a fixed pool of cache blocks."""

    num_blocks: int
    block_size: int
    _free: List[int] = field(default_factory=list)
    _tables: Dict[Any, List[int]] = field(default_factory=dict)
    # pages a request may still claim from the free list (its
    # reservation minus what it has already materialized); admission
    # budgets against free - sum(_pending), so shared pages cost the
    # pool ONCE no matter how many tables map them — that is the
    # prefix-sharing capacity win
    _pending: Dict[Any, int] = field(default_factory=dict)
    _refs: Dict[int, int] = field(default_factory=dict)
    # eviction-priority tier: pages held alive ONLY by a pin (insertion
    # order = pin age); reclaimed oldest-first under allocation
    # pressure, with ``on_reclaim`` notifying the owner (prefix cache)
    _pinned: Dict[int, None] = field(default_factory=dict)
    on_reclaim: Optional[Callable[[List[int]], None]] = None
    high_water: int = 0
    allocs: int = 0
    frees: int = 0
    reclaims: int = 0

    def __post_init__(self):
        self._free = list(range(self.num_blocks))

    @property
    def free_blocks(self) -> int:
        """Pages on the free list (excludes pinned-idle pages)."""
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        """Pages materialized to some request (incl. pinned/shared)."""
        return self.num_blocks - len(self._free)

    @property
    def pending_blocks(self) -> int:
        """Free-list pages promised to live requests but not yet
        materialized (lazy allocation)."""
        return sum(self._pending.values())

    @property
    def committed_blocks(self) -> int:
        """Blocks spoken for: materialized + promised."""
        return self.used_blocks + self.pending_blocks

    @property
    def reclaimable_blocks(self) -> int:
        """Pinned pages with no live holder — the eviction-priority
        tier: counted as capacity for admission, stolen only when the
        free list runs dry."""
        return sum(1 for b in self._pinned if self._refs.get(b) == 1)

    @property
    def available_blocks(self) -> int:
        """Pages an admission may budget against: free-list pages not
        promised to anyone, plus idle pinned pages (reclaimable)."""
        return len(self._free) + self.reclaimable_blocks \
            - self.pending_blocks

    def table(self, rid) -> List[int]:
        """The request's page table: global page ids, in order."""
        return list(self._tables[rid])

    def _lost_reclaimable(self, shared: Sequence[int]) -> int:
        """Idle pinned pages in `shared`: mapping them refcounts them to
        2, so they stop being reclaimable — admission must not count
        them BOTH as free prefix pages and as reclaimable capacity."""
        return sum(1 for b in set(shared)
                   if b in self._pinned and self._refs.get(b) == 1)

    def can_allocate(self, n_tokens: int,
                     shared: Sequence[int] = ()) -> bool:
        """Would an allocation of ``n_tokens`` (minus ``shared`` prefix
        pages) fit the available capacity right now?"""
        need = blocks_for(n_tokens, self.block_size) - len(shared)
        return need <= self.available_blocks \
            - self._lost_reclaimable(shared)

    # -- pinning (prefix residency) ----------------------------------------
    def pin(self, page: int) -> None:
        """Keep `page` resident after its last holder releases it (an
        extra refcount held by the pin)."""
        if page not in self._pinned and page in self._refs:
            self._refs[page] += 1
            self._pinned[page] = None

    def unpin_all(self) -> List[int]:
        """Drop every pin; returns the pages that hit refcount zero
        (returned to the free list) — the hot-swap flush path."""
        released = []
        for page in list(self._pinned):
            self._refs[page] -= 1
            if self._refs[page] == 0:
                del self._refs[page]
                self._free.append(page)
                released.append(page)
        self._pinned.clear()
        self.frees += len(released)
        return released

    def _reclaim(self, n: int) -> None:
        """Steal `n` idle pinned pages (oldest pin first) back onto the
        free list; the owner is told via ``on_reclaim`` so it can drop
        the pages from its prefix cache.  Candidates are collected
        BEFORE any mutation, so an insufficient tier raises with the
        pin bookkeeping (and the owner's prefix cache) fully intact."""
        taken = [page for page in self._pinned
                 if self._refs.get(page) == 1][:n]
        if len(taken) < n:
            raise RuntimeError(
                f"out of cache blocks: need {n - len(taken)} more, "
                f"free {len(self._free)}")
        for page in taken:
            del self._pinned[page]
            del self._refs[page]
            self._free.append(page)
        self.reclaims += len(taken)
        if self.on_reclaim is not None:
            self.on_reclaim(taken)

    def _claim(self, rid, n: int) -> List[int]:
        if n > len(self._free):
            self._reclaim(n - len(self._free))
        got = [self._free.pop() for _ in range(n)]
        for b in got:
            self._refs[b] = 1
        self._tables[rid].extend(got)
        self._pending[rid] -= n
        self.allocs += n
        self.high_water = max(self.high_water, self.used_blocks)
        return got

    def allocate(self, rid, n_tokens: int) -> List[int]:
        """Reserve AND materialize pages for `n_tokens` (eager; the
        dense pool path).  Raises if rid is live or over budget."""
        if rid in self._tables:
            raise ValueError(f"request {rid!r} already holds blocks")
        need = blocks_for(n_tokens, self.block_size)
        if not self.can_allocate(n_tokens):
            raise RuntimeError(
                f"out of cache blocks: need {need}, "
                f"available {self.available_blocks}")
        self._tables[rid] = []
        self._pending[rid] = need
        return self._claim(rid, need)

    def reserve(self, rid, n_tokens: int,
                shared: Sequence[int] = ()) -> None:
        """Budget `n_tokens` for `rid`, mapping `shared` pages (already
        live, refcounted up) as its first pages; the rest materialize
        lazily via :meth:`ensure`.  Shared pages are free — they are
        someone's materialized pages already."""
        if rid in self._tables:
            raise ValueError(f"request {rid!r} already holds blocks")
        need = blocks_for(n_tokens, self.block_size) - len(shared)
        # refcounting the shared pages removes any idle pinned ones
        # from the reclaim tier — budget as if that already happened
        usable = self.available_blocks - self._lost_reclaimable(shared)
        if need > usable:
            raise RuntimeError(
                f"out of cache blocks: need {need}, available {usable}")
        for b in shared:
            self._refs[b] += 1
        self._tables[rid] = list(shared)
        self._pending[rid] = need
        self.high_water = max(self.high_water, self.used_blocks)

    def ensure(self, rid, n_tokens: int) -> List[int]:
        """Materialize physical pages so `rid` can hold `n_tokens`;
        returns the newly claimed page ids (page-overflow allocation).
        Growing past the reservation raises — the scheduler budgets
        prompt + max_new up front precisely so this cannot happen."""
        have = self._tables[rid]
        need = blocks_for(n_tokens, self.block_size) - len(have)
        if need <= 0:
            return []
        if need > self._pending[rid]:
            raise RuntimeError(
                f"request {rid!r} overflows its reservation "
                f"({len(have) + self._pending[rid]} blocks)")
        return self._claim(rid, need)

    def extend(self, rid, n_tokens: int) -> List[int]:
        """Grow a live reservation to cover `n_tokens` total and
        materialize the new pages."""
        need = blocks_for(n_tokens, self.block_size) \
            - len(self._tables[rid])
        if need > self._pending[rid]:
            grow = need - self._pending[rid]
            if grow > self.available_blocks:
                raise RuntimeError(
                    f"out of cache blocks: need {grow}, "
                    f"available {self.available_blocks}")
            self._pending[rid] = need
        return self.ensure(rid, n_tokens)

    def free(self, rid) -> List[int]:
        """Release `rid`'s pages; returns the page ids whose refcount
        hit zero (returned to the free list)."""
        blocks = self._tables.pop(rid)
        self._pending.pop(rid)
        released = []
        for b in blocks:
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                self._free.append(b)
                released.append(b)
        self.frees += len(released)
        return released

    def refcount(self, page: int) -> int:
        """Number of page tables (plus pins) referencing ``page``."""
        return self._refs.get(page, 0)

    def as_dict(self) -> Dict[str, int]:
        """Counters for the ``[serve] pool`` summary line."""
        return {"num_blocks": self.num_blocks,
                "block_size": self.block_size,
                "used_blocks": self.used_blocks,
                "committed_blocks": self.committed_blocks,
                "pinned_blocks": len(self._pinned),
                "block_reclaims": self.reclaims,
                "high_water_blocks": self.high_water,
                "block_allocs": self.allocs, "block_frees": self.frees}


# ---------------------------------------------------------------------------
# CacheLayout protocol
# ---------------------------------------------------------------------------


def _leaf_is_paged(axes_leaf) -> bool:
    return isinstance(axes_leaf, tuple) and "pages" in axes_leaf


def _leaf_is_kv(axes_leaf) -> bool:
    """Attention KV leaves (either layout); everything else but the
    counters is the recurrent state snapshot/restore copies."""
    return isinstance(axes_leaf, tuple) and \
        ("pages" in axes_leaf or "kv_seq" in axes_leaf)


def _leaf_is_counter(axes_leaf) -> bool:
    """Device-side counters (``moe_stats``): neither KV nor per-slot
    state; steps add to them, prefills and rollbacks leave them be."""
    return isinstance(axes_leaf, tuple) and "counters" in axes_leaf


def _axes_leaves(axes):
    is_leaf = (lambda t: isinstance(t, tuple)
               and all(x is None or isinstance(x, str) for x in t))
    return jax.tree.leaves(axes, is_leaf=is_leaf)


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _restore_rec(cache, snap, rec_mask, rows):
    """Masked copy-back of recurrent leaves: rows[b] selects the
    snapshot for slot b (leaves are (stack, num_slots, ...))."""
    flat, tree = jax.tree.flatten(cache)
    it = iter(snap)
    out = []
    for leaf, m in zip(flat, rec_mask):
        if m:
            s = next(it)
            sel = rows.reshape((1, rows.shape[0]) + (1,) * (leaf.ndim - 2))
            out.append(jnp.where(sel, s, leaf))
        else:
            out.append(leaf)
    return jax.tree.unflatten(tree, out)


class CacheLayout:
    """Family-agnostic cache protocol the serving stack decodes through.

    A layout owns the physical cache pytree and implements, per layer
    leaf, the five operations :class:`repro.serve.session.
    DecodeSession` is written against:

    ==========  =========================================================
    init        build the cache leaves (``lm.init_cache``, dense or
                ``pages=``)
    write       land prefilled KV/state (``insert`` / ``insert_prefill``)
                and route decode-step writes (slot rows / block tables)
    read        ``step_kwargs()`` — the extra arrays one decode step
                needs (``tables`` for paged, nothing for slots)
    snapshot    copy out the recurrent leaves (mamba / xLSTM state)
    restore     masked copy-back per slot — the speculative-decoding
                rollback primitive (attention KV never rolls back: stale
                positions are causally masked and overwritten)
    ==========  =========================================================

    Slot bookkeeping (`admit` / `release` / `slot_of`) is shared here;
    page accounting is the paged subclass's :class:`BlockManager`.
    """

    cfg: ModelConfig
    num_slots: int
    cache: Any
    rec_mask: Tuple[bool, ...]

    def _init_slots(self, num_slots: int) -> None:
        self.num_slots = num_slots
        self._free_slots = list(range(num_slots))
        self._slot_of: Dict[Any, int] = {}

    @property
    def free_slots(self) -> int:
        """Decode slots not currently assigned to a request."""
        return len(self._free_slots)

    def slot_of(self, rid) -> int:
        """The decode-batch row assigned to ``rid``."""
        return self._slot_of[rid]

    @property
    def has_recurrent(self) -> bool:
        """True when the stack carries per-slot recurrent state (hybrid
        / ssm families) — the leaves snapshot/restore operates on."""
        return any(self.rec_mask)

    @property
    def supports_row_subset(self) -> bool:
        """True when a decode step may cover any subset of rows (no
        cache leaf is indexed by slot) — what lets the scheduler group
        ragged rows by gather width."""
        return False

    def step_kwargs(self, width: Optional[int] = None,
                    rows: Optional[np.ndarray] = None) -> Dict[str, Any]:
        """Extra per-step arrays for :func:`repro.models.lm.lm_decode`."""
        return {}

    def snapshot(self) -> Tuple[jax.Array, ...]:
        """Copy of the recurrent leaves (empty for attention-only
        stacks, where rollback is free)."""
        flat = jax.tree.leaves(self.cache)
        return tuple(jnp.array(x, copy=True)
                     for x, m in zip(flat, self.rec_mask) if m)

    def restore(self, snap: Tuple[jax.Array, ...], rows) -> None:
        """Roll slots with ``rows[b] == True`` back to ``snap``."""
        if not snap:
            return
        self.cache = _restore_rec(self.cache, snap, self.rec_mask,
                                  jnp.asarray(np.asarray(rows, bool)))


# ---------------------------------------------------------------------------
# paged physical pool
# ---------------------------------------------------------------------------


class PageShard:
    """Host-side accounting for ONE page-pool shard.

    A :class:`BlockManager` over shard-LOCAL page ids plus the prefix
    cache (copy-on-admit sharing, pinning, weight-epoch invalidation).
    The ordinary single-device :class:`PagedLayout` holds exactly one;
    the serving mesh holds one per ``data`` shard, each the private
    accountant of that shard's slice of the physical pool — admission,
    eviction and prefix decisions never consult another shard, which is
    what keeps them host-local on a multi-host mesh.  ``offset`` is the
    shard's base in the GLOBAL page-id space block tables use: local
    page ``p`` is global page ``offset + p`` and the shard's null page
    is ``offset + num_pages``.
    """

    def __init__(self, num_pages: int, block_size: int,
                 pin_prefix: bool = False, offset: int = 0):
        self.blocks = BlockManager(num_pages, block_size)
        self.blocks.on_reclaim = self._evict
        self.null_page = num_pages              # local id
        self.offset = offset
        self.pin_prefix = bool(pin_prefix)
        # prefix cache: chained token-chunk key -> canonical physical
        # page, plus every live page known to hold that content (a
        # follower that prefilled its own copy before the prefix was
        # registered is still a valid donor once the original dies)
        self._prefix: Dict[Any, int] = {}
        self._key_pages: Dict[Any, set] = {}
        self._page_key: Dict[int, Any] = {}
        # per-rid incremental registration cursor: (pages done, last key)
        self._reg_state: Dict[Any, Tuple[int, Any]] = {}
        # weight epoch: bumped by invalidate_prefix() on hot swap so
        # pages computed under old weights are never shared forward
        self._epoch = 0
        self._admit_epoch: Dict[Any, int] = {}
        self.prefix_hits = 0
        self.prefix_shared_tokens = 0

    # -- prefix sharing ----------------------------------------------------
    @staticmethod
    def _chunk_keys(prompt: np.ndarray, block_size: int, start: int = 0,
                    prev=None):
        """Chained keys for fully-filled prompt pages ``start..``: key_i
        commits to ALL tokens up to and including page i (so equal keys
        mean equal prefixes, not just equal pages).  ``prev`` must be
        the chain key of page ``start - 1`` when resuming."""
        keys = []
        for i in range(start, len(prompt) // block_size):
            prev = _chain_key(prev,
                              prompt[i * block_size:(i + 1) * block_size])
            keys.append(prev)
        return keys

    def probe_prefix(self, key_at, max_pages: int) -> List[int]:
        """Longest live prefix run in this shard using externally
        derived chain keys (``key_at(i)`` -> key of page i).  The
        sharded layout derives the keys ONCE (memoized) and probes
        every shard with the same supplier, so a D-shard admission
        check hashes the prompt once, not D times."""
        pages = []
        for i in range(max_pages):
            page = self._prefix.get(key_at(i))
            if page is None or self.blocks.refcount(page) == 0:
                break
            pages.append(page)
        return pages

    def find_shared_prefix(self, prompt: np.ndarray
                           ) -> Tuple[List[int], int]:
        """Longest registered prefix of `prompt` in live LOCAL pages.

        Returns (local page ids, shared token count).  Capped at
        ``len(prompt) - 1`` so at least one suffix token is always
        prefilled (its hidden state supplies the first sampled token).
        Keys are derived lazily page by page, so a miss on page 0 costs
        one chunk hash — this runs on every admission check.
        """
        bs = self.blocks.block_size
        max_pages = (len(prompt) - 1) // bs
        pages = self.probe_prefix(_prefix_key_memo(prompt, bs),
                                  max_pages)
        return pages, len(pages) * bs

    def admit(self, rid, n_tokens: int,
              shared: Tuple[List[int], int]) -> None:
        """Page-budget side of an admission: reserve ``n_tokens`` with
        ``shared`` (local prefix pages) mapped in, stamp the weight
        epoch, and resume the registration cursor past the shared
        pages."""
        shared_pages, shared_len = shared
        self.blocks.reserve(rid, n_tokens, shared=shared_pages)
        self._admit_epoch[rid] = self._epoch
        if shared_pages:
            self.prefix_hits += 1
            self.prefix_shared_tokens += shared_len
            # registration resumes after the shared pages — their keys
            # are already in the cache
            self._reg_state[rid] = (len(shared_pages),
                                    self._page_key[shared_pages[-1]])

    def register_prefix(self, rid, prompt: np.ndarray) -> None:
        """Offer `rid`'s fully-filled prompt pages to future requests.

        Incremental: per-chunk calls during chunked prefill only hash
        the pages filled since the last call, resuming the key chain
        instead of re-deriving it from page 0 every time.  Requests
        admitted before the last weight swap are refused — their pages
        (or their pages' attention context) came from the old model.
        """
        if self._admit_epoch.get(rid, -1) != self._epoch:
            return
        table = self.blocks.table(rid)
        start, prev = self._reg_state.get(rid, (0, None))
        keys = self._chunk_keys(prompt, self.blocks.block_size,
                                start=start, prev=prev)
        for i, key in zip(range(start, start + len(keys)), keys):
            if i >= len(table):
                break
            page = table[i]
            if self._page_key.get(page) != key:
                self._page_key[page] = key
                self._key_pages.setdefault(key, set()).add(page)
                self._prefix.setdefault(key, page)
            if self.pin_prefix:
                # eviction-priority residency: the page survives its
                # holders (reclaimed oldest-first under pressure)
                self.blocks.pin(page)
            self._reg_state[rid] = (i + 1, key)

    def _evict(self, released_pages: List[int]) -> None:
        """Drop freed pages from the prefix cache; if a freed page was
        the canonical holder of its key, re-point the key at another
        live copy before giving up on it."""
        for page in released_pages:
            key = self._page_key.pop(page, None)
            if key is None:
                continue
            copies = self._key_pages.get(key, set())
            copies.discard(page)
            if self._prefix.get(key) == page:
                if copies:
                    self._prefix[key] = next(iter(copies))
                else:
                    self._prefix.pop(key, None)
            if not copies:
                self._key_pages.pop(key, None)

    def release(self, rid) -> None:
        """Drop the request's pages (prefix-shared ones survive as
        cache entries until evicted or invalidated)."""
        self._reg_state.pop(rid, None)
        self._admit_epoch.pop(rid, None)
        self._evict(self.blocks.free(rid))

    def invalidate_prefix(self) -> None:
        """Flush the prefix cache (hot swap): pages computed under the
        old weights must not be mapped into post-swap admissions, and
        still-prefilling pre-swap requests stop registering (their
        remaining chunks attend over old-weight history).  Pins die
        with the index — a pinned page's whole value is being shareable.
        Live tables and refcounts are untouched."""
        self._prefix.clear()
        self._key_pages.clear()
        self._page_key.clear()
        self.blocks.unpin_all()
        self._epoch += 1


def _prefix_key_memo(prompt: np.ndarray, block_size: int):
    """Lazy chain-key supplier for ``prompt``: ``key_at(i)`` hashes
    chunks only up to page i, memoized — a page-0 miss still costs one
    hash, and multiple shard probes share one derivation."""
    keys: List[bytes] = []

    def key_at(i: int):
        while len(keys) <= i:
            j = len(keys)
            keys.append(_chain_key(keys[-1] if keys else None,
                                   prompt[j * block_size:(j + 1)
                                          * block_size]))
        return keys[i]

    return key_at


def _insert_leaf_paged(dst, src, page_ids, offsets):
    """Scatter a (stack, 1, S, Hkv, D) dense prefill leaf into the
    (stack, P+1, bs, Hkv, D) pool at (page_ids[s], offsets[s])."""
    return dst.at[:, page_ids, offsets].set(src[:, 0].astype(dst.dtype))


def _insert_leaf_slot(dst, src, slot):
    """Write a (stack, 1, ...) recurrent-state leaf into pool row `slot`."""
    start = (0, jnp.asarray(slot, jnp.int32)) + (0,) * (dst.ndim - 2)
    return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), start)


@partial(jax.jit, static_argnums=(1,), donate_argnums=(0,))
def _insert_tree_paged(pool, paged_mask, src, page_ids, offsets, slot):
    """``paged_mask`` per pool leaf: True a page pool, False a slot row,
    None a counter the prefill cache does not hold (kept as it is)."""
    flat_pool, tree = jax.tree.flatten(pool)
    flat_src = iter(jax.tree.leaves(src))
    out = []
    for d, paged in zip(flat_pool, paged_mask):
        if paged is None:
            out.append(d)
        elif paged:
            out.append(_insert_leaf_paged(d, next(flat_src), page_ids,
                                          offsets))
        else:
            out.append(_insert_leaf_slot(d, next(flat_src), slot))
    return jax.tree.unflatten(tree, out)


class PagedLayout(CacheLayout):
    """Paged decode cache: shared page pools + per-slot block tables.

    ``num_slots`` bounds the decode batch width (and the number of
    recurrent-state rows); memory capacity is ``num_pages *
    block_size`` tokens shared by every request.  ``max_seq`` caps a
    single request (it sizes the block-table width) and defaults to the
    whole pool — the per-slot ``max_len`` ceiling of the dense layout
    is gone.  With ``pin_prefix=True`` registered prompt pages stay
    resident after their holders release (reclaimed oldest-first under
    pressure).

    **Sharded mode** (``data_shards > 1``, the serving mesh): slots and
    pages split into ``data_shards`` equal groups; group i's slots can
    only map group i's pages, each group is accounted by its own
    host-local :class:`PageShard` (admission, prefix cache, pinning,
    reclaim), and each group ends with its own null page — block
    tables hold GLOBAL page ids ``shard.offset + local``, which is how
    the shard_map gather (:func:`repro.kernels.ops.paged_attention`)
    rebases to a shard-local index without ever touching another
    shard's pool.  ``placer`` (mesh use) maps the freshly initialized
    cache pytree + its logical axes to device-placed arrays.
    """

    def __init__(self, cfg: ModelConfig, num_slots: int, num_pages: int,
                 block_size: int = 16, max_seq: Optional[int] = None,
                 pin_prefix: bool = False, data_shards: int = 1,
                 placer=None):
        self.cfg = cfg
        self.block_size = block_size
        self.data_shards = int(data_shards)
        if num_slots % self.data_shards or num_pages % self.data_shards:
            raise ValueError(
                f"num_slots ({num_slots}) and num_pages ({num_pages}) "
                f"must be divisible by data_shards ({data_shards})")
        pps = num_pages // self.data_shards     # usable pages per shard
        self._slots_per_shard = num_slots // self.data_shards
        shard_tokens = pps * block_size
        self.max_seq = min(max_seq or shard_tokens, shard_tokens)
        self.max_blocks_per_seq = blocks_for(self.max_seq, block_size)
        self.shards = tuple(
            PageShard(pps, block_size, pin_prefix=pin_prefix,
                      offset=i * (pps + 1))
            for i in range(self.data_shards))
        # shard 0's global null page — THE null page in the single-shard
        # layout (== num_pages, as before); sharded callers use
        # null_page_of(slot)
        self.null_page = pps
        self.pin_prefix = bool(pin_prefix)
        # physical pool: every shard's pages + its null page,
        # contiguous in global id order
        total = self.data_shards * (pps + 1)
        self.cache, axes = lm.init_cache(cfg, num_slots,
                                         pages=(total - 1, block_size))
        self.paged_mask = tuple(_leaf_is_paged(a)
                                for a in _axes_leaves(axes))
        self.counter_mask = tuple(_leaf_is_counter(a)
                                  for a in _axes_leaves(axes))
        self.rec_mask = tuple(not (_leaf_is_kv(a) or _leaf_is_counter(a))
                              for a in _axes_leaves(axes))
        if placer is not None:
            self.cache = placer(self.cache, axes)
        self._init_slots(num_slots)
        self.tables = np.empty((num_slots, self.max_blocks_per_seq),
                               np.int32)
        for s in range(num_slots):
            self.tables[s, :] = self.null_page_of(s)
        self._shard_of_rid: Dict[Any, int] = {}
        self._share_shard: Optional[int] = None
        # pool-WIDE concurrent page peak (sharded mode): summing the
        # per-shard high waters would overstate it when shards peak at
        # different times
        self._hw_total = 0

    # -- shard routing -----------------------------------------------------
    def shard_of_slot(self, slot: int) -> int:
        """The data shard whose sub-pool holds this slot's pages."""
        return slot // self._slots_per_shard

    def null_page_of(self, slot: int) -> int:
        """The slot's shard-local scratch page (global id) — where
        idle rows scatter their dead writes."""
        shard = self.shards[self.shard_of_slot(slot)]
        return shard.offset + shard.null_page

    @property
    def blocks(self) -> BlockManager:
        """Shard 0's manager — THE manager in the single-shard layout;
        geometry reference (block_size / num_blocks are per-shard and
        identical across shards) for sharded callers."""
        return self.shards[0].blocks

    @property
    def prefix_hits(self) -> int:
        """Prefix-cache hits, summed over shards."""
        return sum(s.prefix_hits for s in self.shards)

    @property
    def prefix_shared_tokens(self) -> int:
        """Prompt tokens served from shared prefix pages, all shards."""
        return sum(s.prefix_shared_tokens for s in self.shards)

    def _free_slots_in(self, shard_i: int) -> List[int]:
        lo = shard_i * self._slots_per_shard
        hi = lo + self._slots_per_shard
        return [s for s in self._free_slots if lo <= s < hi]

    def _choose_shard(self, n_tokens: int,
                      shared_pages: Sequence[int] = (),
                      hint: Optional[int] = None) -> Optional[int]:
        """Deterministic admission target: the prefix-hinted shard when
        it still fits, else the free-slot shard with the most available
        pages (lowest index on ties) — None when nowhere fits."""
        if hint is not None and self._free_slots_in(hint) and \
                self.shards[hint].blocks.can_allocate(
                    n_tokens, shared=shared_pages):
            return hint
        best, best_avail = None, -1
        for i, shard in enumerate(self.shards):
            if not self._free_slots_in(i):
                continue
            if not shard.blocks.can_allocate(n_tokens):
                continue
            if shard.blocks.available_blocks > best_avail:
                best, best_avail = i, shard.blocks.available_blocks
        return best

    def peek_shard(self, n_tokens: int,
                   shared_pages: Sequence[int] = ()) -> Optional[int]:
        """The shard :meth:`admit` would pick right now (no mutation) —
        lets the mesh scheduler pre-check the drafter's mirror pool in
        the SAME shard before committing an admission."""
        hint = self._share_shard if shared_pages else None
        return self._choose_shard(n_tokens, shared_pages, hint)

    # -- prefix sharing ----------------------------------------------------
    def find_shared_prefix(self, prompt: np.ndarray
                           ) -> Tuple[List[int], int]:
        """Longest registered prefix of `prompt` over the shards an
        admission could land in (LOCAL page ids of the winning shard,
        recorded for the admit that follows).  Single-shard: exactly
        the PR-3/4 behavior.  The chain keys are derived once and
        shared by every shard's probe."""
        bs = self.block_size
        max_pages = (len(prompt) - 1) // bs
        key_at = _prefix_key_memo(prompt, bs)
        best, best_shard = ([], 0), None
        for i, shard in enumerate(self.shards):
            if self.data_shards > 1 and not self._free_slots_in(i):
                continue        # a match in a slot-full shard is unusable
            pages = shard.probe_prefix(key_at, max_pages)
            if len(pages) * bs > best[1]:
                best, best_shard = (pages, len(pages) * bs), i
        self._share_shard = best_shard if best[0] else None
        return best

    def register_prefix(self, rid, prompt: np.ndarray) -> None:
        """Publish ``rid``'s prompt pages into its shard's prefix cache."""
        self.shards[self._shard_of_rid[rid]].register_prefix(rid, prompt)

    # -- slot / page lifecycle ---------------------------------------------
    @property
    def supports_row_subset(self) -> bool:
        """Whether a decode step may cover an arbitrary subset of rows."""
        # with no recurrent rows, every cache leaf is a shared pool —
        # a decode step may cover any subset of slots (ragged grouping;
        # single-shard only: sharded steps must keep every row in its
        # shard's batch partition)
        return not self.has_recurrent and self.data_shards == 1

    def step_kwargs(self, width: Optional[int] = None,
                    rows: Optional[np.ndarray] = None) -> Dict[str, Any]:
        """Page tables (optionally width-clipped / row-subset) for the
        decode dispatch."""
        W = width if width is not None else self.max_blocks_per_seq
        tables = self.tables if rows is None else self.tables[rows]
        return {"tables": jnp.asarray(tables[:, :W])}

    def can_admit(self, n_tokens: int,
                  shared_pages: Sequence[int] = ()) -> bool:
        """Can some shard hold ``n_tokens`` (given ``shared_pages``
        already mapped) with a free slot to go with it?"""
        if not self._free_slots or n_tokens > self.max_seq:
            return False
        hint = self._share_shard if shared_pages else None
        return self._choose_shard(n_tokens, shared_pages, hint) is not None

    def admit(self, rid, n_tokens: int,
              prompt: Optional[np.ndarray] = None,
              shared: Optional[Tuple[List[int], int]] = None,
              slot: Optional[int] = None) -> Tuple[int, int]:
        """Claim a slot + a token-budget reservation for `rid`.

        With `prompt` given, maps any prefix-cached pages into the new
        table (copy-on-admit sharing); pass ``shared`` to reuse a
        :meth:`find_shared_prefix` result the admission check already
        computed instead of hashing the prompt again.  ``slot`` forces
        a specific slot (the drafter's mirror pool must admit into the
        target's slot so the two decode batches stay row-aligned).
        Returns (slot, shared_len).
        """
        if not self._free_slots:
            raise RuntimeError("no free cache slots")
        if n_tokens > self.max_seq:
            raise ValueError(
                f"request needs {n_tokens} tokens > pool max_seq "
                f"{self.max_seq}")
        if shared is None:
            shared = ([], 0) if prompt is None else \
                self.find_shared_prefix(prompt)
        shared_pages, shared_len = shared
        hint = self._share_shard if shared_pages else None
        if slot is None:
            shard_i = self._choose_shard(n_tokens, shared_pages, hint)
            if shard_i is None:
                shard_i = self.shard_of_slot(self._free_slots[-1])
            # LIFO within the shard, matching the old single-list pop()
            slot = self._free_slots_in(shard_i)[-1]
        else:
            if slot not in self._free_slots:
                raise RuntimeError(f"slot {slot} is not free")
            shard_i = self.shard_of_slot(slot)
        if hint is not None and hint != shard_i:
            # the prefix lives in another shard's pool — unusable here
            shared_pages, shared_len = [], 0
        self.shards[shard_i].admit(rid, n_tokens,
                                   (shared_pages, shared_len))
        self._free_slots.remove(slot)
        self._slot_of[rid] = slot
        self._shard_of_rid[rid] = shard_i
        off = self.shards[shard_i].offset
        self.tables[slot, :] = self.null_page_of(slot)
        if shared_pages:
            self.tables[slot, :len(shared_pages)] = \
                off + np.asarray(shared_pages, np.int32)
        self._note_usage()
        return slot, shared_len

    def ensure(self, rid, n_tokens: int) -> None:
        """Materialize pages so `rid` can hold `n_tokens`; updates the
        slot's block table in place (global ids)."""
        slot = self._slot_of[rid]
        shard = self.shards[self._shard_of_rid[rid]]
        have = len(shard.blocks.table(rid))
        new = shard.blocks.ensure(rid, n_tokens)
        if new:
            self.tables[slot, have:have + len(new)] = \
                shard.offset + np.asarray(new, np.int32)
            self._note_usage()

    def _note_usage(self) -> None:
        if self.data_shards > 1:
            used = sum(s.blocks.used_blocks for s in self.shards)
            self._hw_total = max(self._hw_total, used)

    def insert_prefill(self, rid, prefill_cache, prompt_len: int) -> None:
        """Scatter a (batch=1) dense prefill cache into the pool.

        The one-shot path for recurrent/hybrid families: attention
        leaves scatter token s into (table[s // bs], s % bs); recurrent
        state leaves overwrite the request's slot row.
        """
        self.ensure(rid, prompt_len)
        slot = self._slot_of[rid]
        shard = self.shards[self._shard_of_rid[rid]]
        table = [shard.offset + p for p in shard.blocks.table(rid)]
        # per-token page targets; positions past prompt_len (padding)
        # are dropped onto the row's shard's null page
        kv_len = _first_kv_len(prefill_cache, [
            p for p, c in zip(self.paged_mask, self.counter_mask) if not c])
        if kv_len is None:          # pure-recurrent stack: no KV pages
            kv_len = prompt_len
        pos = np.arange(kv_len)
        pids = np.full((kv_len,), self.null_page_of(slot), np.int32)
        valid = pos < prompt_len
        pids[valid] = np.asarray(table, np.int32)[pos[valid]
                                                  // self.block_size]
        offs = (pos % self.block_size).astype(np.int32)
        mask = tuple(None if c else p
                     for p, c in zip(self.paged_mask, self.counter_mask))
        self.cache = _insert_tree_paged(
            self.cache, mask, prefill_cache,
            jnp.asarray(pids), jnp.asarray(offs), jnp.int32(slot))

    def release(self, rid) -> int:
        """Free `rid`'s slot + page refs; returns the freed slot."""
        slot = self._slot_of.pop(rid)
        self._free_slots.append(slot)
        self.tables[slot, :] = self.null_page_of(slot)
        self.shards[self._shard_of_rid.pop(rid)].release(rid)
        return slot

    def invalidate_prefix(self) -> None:
        """Flush every shard's prefix cache + pins (hot swap)."""
        for shard in self.shards:
            shard.invalidate_prefix()

    @property
    def bytes_per_token(self) -> int:
        """Device bytes the pools hold for one token, over every layer
        (page pools' bytes over their capacity in tokens)."""
        leaves = [x for x, p in zip(jax.tree.leaves(self.cache),
                                    self.paged_mask) if p]
        tokens = self.data_shards * (self.shards[0].blocks.num_blocks + 1) \
            * self.block_size
        return sum(x.nbytes for x in leaves) // max(tokens, 1)

    def moe_counts(self) -> Optional[Dict[str, np.ndarray]]:
        """The device's routing counters copied to the host (one copy;
        None when the stack keeps none): ``expert_tokens`` (layers, E)
        and ``dropped_tokens`` (layers,)."""
        if not (isinstance(self.cache, dict) and "moe_stats" in self.cache):
            return None
        return {k: np.asarray(v) for k, v in self.cache["moe_stats"].items()}

    def reset_moe_counts(self) -> None:
        """Zero the routing counters (device-side; nothing is copied)."""
        if isinstance(self.cache, dict) and "moe_stats" in self.cache:
            self.cache = {**self.cache, "moe_stats": jax.tree.map(
                jnp.zeros_like, self.cache["moe_stats"])}

    def table_width_for(self, max_tokens: int) -> int:
        """Block-table columns needed to cover `max_tokens` (the
        scheduler buckets this so gather width tracks the batch's true
        maximum instead of always paying max_blocks_per_seq)."""
        return min(self.max_blocks_per_seq,
                   blocks_for(max(max_tokens, 1), self.block_size))

    def as_dict(self) -> Dict[str, int]:
        """Pool summary: slot/prefix counters + shard-aggregated block
        accounting."""
        d = {"num_slots": self.num_slots, "max_seq": self.max_seq,
             "free_slots": self.free_slots,
             "prefix_hits": self.prefix_hits,
             "prefix_shared_tokens": self.prefix_shared_tokens,
             "data_shards": self.data_shards}
        agg = self.shards[0].blocks.as_dict()
        for shard in self.shards[1:]:
            for k, v in shard.blocks.as_dict().items():
                if k != "block_size":
                    agg[k] += v
        if self.data_shards > 1:
            # the pool-wide CONCURRENT peak, not the sum of per-shard
            # peaks (which overstates when shards peak at different
            # times)
            agg["high_water_blocks"] = self._hw_total
        return {**d, **agg}


def _first_kv_len(prefill_cache, paged_mask) -> Optional[int]:
    """Sequence length of the first attention leaf of a dense (batch=1)
    prefill cache: leaves are (stack, 1, S, Hkv, D).  None for pure-
    recurrent stacks (xLSTM), whose cache is all per-slot state rows."""
    for leaf, paged in zip(jax.tree.leaves(prefill_cache),
                           paged_mask):
        if paged:
            return int(leaf.shape[2])
    return None


# ---------------------------------------------------------------------------
# dense slot layout (the PR-2 baseline, kept for layout="dense")
# ---------------------------------------------------------------------------


def _insert_row(dst: jax.Array, src: jax.Array, slot) -> jax.Array:
    """Write `src` (leading (layers, 1, ...)) into pool row `slot`.

    Every cache leaf is (layers, batch, *state); attention leaves carry
    a kv_seq axis shorter than the pool's max_len at prefill time — pad
    with zeros so the whole row is overwritten (slot reuse must not
    leak the previous occupant's cache).
    """
    if src.shape[2:] != dst.shape[2:]:
        pad = [(0, 0), (0, 0)] + [(0, d - s)
                                  for d, s in zip(dst.shape[2:], src.shape[2:])]
        src = jnp.pad(src, pad)
    start = (0, jnp.asarray(slot, jnp.int32)) + (0,) * (dst.ndim - 2)
    return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype), start)


# the pool is donated: the caller always rebinds CachePool.cache to the
# result, so the update happens in place instead of copying the whole
# preallocated pool on every request admission
@partial(jax.jit, donate_argnums=(0,))
def _insert_tree(pool, src, slot):
    return jax.tree.map(lambda d, s: _insert_row(d, s, slot), pool, src)


@partial(jax.jit, donate_argnums=(0,))
def _insert_tree_batch(pool, src):
    return jax.tree.map(lambda d, s: _insert_row(d, s, 0), pool, src)


class SlotLayout(CacheLayout):
    """One preallocated dense decode cache shared by all requests.

    ``cache`` holds `num_slots` rows of `max_len` tokens (allocated once
    at construction via :func:`repro.models.lm.init_cache`); slot and
    page lifetime are managed here so the scheduler only deals in
    request ids.  Pages are bookkeeping only — a request's cache is its
    contiguous slot row, which is what the paged layout replaces.
    """

    def __init__(self, cfg: ModelConfig, num_slots: int, max_len: int,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 placer=None):
        self.cfg = cfg
        self.max_len = max_len
        self.blocks = BlockManager(
            num_blocks if num_blocks is not None
            else num_slots * blocks_for(max_len, block_size),
            block_size)
        self.cache, axes = lm.init_cache(cfg, num_slots, max_len)
        self.rec_mask = tuple(not _leaf_is_kv(a)
                              for a in _axes_leaves(axes))
        if placer is not None:
            self.cache = placer(self.cache, axes)
        self._init_slots(num_slots)

    def can_admit(self, n_tokens: int) -> bool:
        """Room for a request reserving `n_tokens` (prompt + max new)?"""
        return bool(self._free_slots) and n_tokens <= self.max_len \
            and self.blocks.can_allocate(n_tokens)

    def admit(self, rid, n_tokens: int,
              slot: Optional[int] = None) -> int:
        """Claim a slot + pages for `rid`; returns the slot index.
        ``slot`` forces a specific one (drafter mirror pools must stay
        row-aligned with the target's)."""
        if not self._free_slots:
            raise RuntimeError("no free cache slots")
        if n_tokens > self.max_len:
            raise ValueError(
                f"request needs {n_tokens} tokens > pool max_len "
                f"{self.max_len}")
        self.blocks.allocate(rid, n_tokens)
        if slot is None:
            slot = self._free_slots.pop()
        else:
            self._free_slots.remove(slot)
        self._slot_of[rid] = slot
        return slot

    def insert(self, rid, prefill_cache) -> None:
        """Overwrite `rid`'s slot row with a (batch=1) prefilled cache."""
        self.cache = _insert_tree(self.cache, prefill_cache,
                                  jnp.int32(self._slot_of[rid]))

    def insert_batch(self, prefill_cache) -> None:
        """Overwrite ALL slot rows with a (batch=num_slots) prefilled
        cache — the engine path, where one uniform-length batch fills
        the whole pool at once."""
        B = jax.tree.leaves(prefill_cache)[0].shape[1]
        assert B == self.num_slots, (B, self.num_slots)
        self.cache = _insert_tree_batch(self.cache, prefill_cache)

    def release(self, rid) -> int:
        """Free `rid`'s slot + pages; returns the freed slot index."""
        slot = self._slot_of.pop(rid)
        self._free_slots.append(slot)
        self.blocks.free(rid)
        return slot

    def as_dict(self) -> Dict[str, int]:
        """Counters for the ``[serve] pool`` summary line."""
        return {"num_slots": self.num_slots, "max_len": self.max_len,
                "free_slots": self.free_slots, **self.blocks.as_dict()}



# legacy names (PR-2/PR-3): the pools ARE the layouts now
CachePool = SlotLayout
PagedCachePool = PagedLayout
