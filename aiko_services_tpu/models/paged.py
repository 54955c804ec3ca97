"""Paged KV cache for continuous-batching LLM serving (ISSUE 8).

The monolithic serving cache (``llama.init_cache``) allocates
``[slots, max_seq]`` up front: every slot pays worst-case sequence
memory whether it holds a 12-token chat turn or an 8k document, and the
slot extent is welded into the compiled decode step.  This module stores
KV in fixed-size **pages** instead:

- one physical **pool** per cache side, ``[L, P, page_tokens, K*hd]``
  (int8 caches pair it with a ``[L, P, page_tokens, K, 1]`` scale pool
  -- the per-token-per-head scales ride their page); a LATENT cache
  (models/deepseek.py) is ONE pool under the key ``latent``, ``[L, P,
  W, page_tokens]`` -- a page's tokens lie along the LAST axis -- behind
  the same table and allocator; a config that says which of its layers
  own pages (``n_paged_layers``: models/olmo_hybrid.py, whose recurrent
  layers own none) gets pools that deep, and what it keeps PER SLOT
  beside them (``slot_state``: the recurrent state and the convolution's
  tail, ``[L_lin, slots, ...]``, addressed by the slot and by no page
  table; models/falcon_h1.py, whose every layer owns pages AND a
  state, lays both over all its layers) rides the same cache dict --
  one ``init_paged_cache``, one allocator, one ``recover()``; a config
  whose layers of one kind
  attend a sliding WINDOW (``n_window_layers`` of ``window`` tokens:
  models/command_a.py) gets a second pair of pools for them,
  ``window_k`` / ``window_v`` ``[L_win, P_win, page_tokens, K*hd]``,
  behind a second table, ``window_table``, from which the allocator
  takes back every page that lies wholly behind the window (the pages
  the window layers hold stay bounded whatever the context), while the
  other layers keep every page;
- a device **page table** ``[B, pages_per_slot] int32`` mapping each
  slot's logical pages to physical pages.  Entry 0 is the reserved
  TRASH page: unallocated logical pages point at it, and inactive
  batch rows route their decode writes there (the paged twin of the
  dense path's ``max_seq - 1`` trash position);
- a host-side :class:`PageAllocator` (free list + per-slot
  assignments).  Admission takes pages as prompts actually need them,
  decode grows a slot page-at-a-time, and eviction returns the slot's
  pages to the pool -- ragged lengths stop forcing worst-case
  allocation, and admit/evict never changes a compiled shape (the pool
  and table shapes are static; only table *values* change).

Device access goes through gather/scatter:
``llama.prefill_into_slot(s)`` / ``decode_step`` / ``decode_loop``
detect a paged cache (:func:`is_paged`) and (a) gather a slot's pages
into the contiguous row view their attention already consumes, (b)
scatter KV writes through the table with ``dynamic_update_slice`` AFTER
the layer scan, on the stacked pool (:func:`scatter_pages` per covered
page for admission, ``llama._scatter_positions`` per position for
decode).  The pool never rides the layer scan as ``xs``/``ys``: a scan
input is read-only, so each step copied its layer out of the pool and
the scan stacked the updated layers into a FRESH pool that donation
could not alias -- the v5e trace showed four pool-sized slice /
update-slice fusions and two pool copies per admission chunk (71 ms
against 18 ms for the same chunk beside a pool a sixth the size;
PERF.md, PR 24/27).  Admission reads only the slot's own pages of each
layer (:func:`gather_rows`) and writes only the chunk's pages.  The
decode-side gather materializes the logical view, so the
REFERENCE paged decode streams the cache roughly twice per step on TPU
-- the price of paging without a paged-attention kernel.  ISSUE 11
removed that price on the kernel plane: when the decode backend
resolves to ``paged-kernel`` (ops.decode_backend -- 'auto' on the
chip at any extent, or an explicit flash/``decode_kernel`` request),
decode and chunk-verify walk the page table IN-KERNEL
(ops/pallas_decode.py:flash_decode_attention_paged): each row copies
its own live pages out of the pool, their physical indices read from
the scalar-prefetched table, so the logical row view never
materializes, the cache streams once, and a page a slot could hold
but does not is never touched.  The gather path remains the reference
(and the off-chip / distributed fallback); the memory win (pool
sized to the *live* token count) and recompile-free admission hold on
both.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .quant import is_quantized

__all__ = ["PageAllocator", "init_paged_cache", "is_paged",
           "pages_per_slot", "pool_page_tokens", "paged_extent",
           "gather_layer", "gather_rows", "gather_slot", "scatter_pages",
           "gather_latent_pages", "latent_pages", "scatter_latent_pages",
           "scatter_latent_rows", "prefix_page_keys"]


def pages_per_slot(max_seq: int, page_tokens: int) -> int:
    if page_tokens <= 0 or max_seq % page_tokens:
        raise ValueError(
            f"kv_page_tokens={page_tokens}: must divide max_seq "
            f"({max_seq})")
    return max_seq // page_tokens


def init_paged_cache(config, batch: int, max_seq: int | None = None,
                     page_tokens: int = 64,
                     total_pages: int | None = None,
                     window_pages: int | None = None) -> dict:
    """Paged serving cache: ``{"k": pool, "v": pool, "page_table"}``,
    or, for a config with a ``latent_width`` (latent attention: one
    payload a token, no heads, no k/v pair), ``{"latent": pool,
    "page_table"}`` with the pool ``[L, P, latent_width, page_tokens]``
    behind the same table and allocator.  A config with
    ``n_paged_layers`` (the layers that own pages: some or all) gets
    k/v pools that deep, and its ``slot_state`` (``name -> (leading
    shape, shape a slot, dtype)``) laid out ``[*leading, batch, ...]``
    under each name beside them, zero: what a slot holds that no page
    addresses (the two are independent: a layer may own both).  A
    latent page is stored TOKENS-MINOR: a width that is no multiple of
    the 128 lanes (576) makes that the v5e's own default layout of the
    array, and a pool
    declared rows-minor was transposed -- three passes over 2.4 GB --
    at the entry of every program that took it (PERF.md, PR 29).

    ``total_pages`` counts PHYSICAL pages including the reserved trash
    page 0 (default: full provisioning, ``batch * pages_per_slot + 1``
    -- memory parity with the dense cache; size it down to serve more
    slots than worst-case memory allows, with the ContinuousBatcher
    preempting under pool pressure).  A config with
    ``n_window_layers`` also gets ``window_k`` / ``window_v`` pools that
    deep of ``window_pages`` physical pages (default: full
    provisioning, as ``total_pages``) and their own ``window_table``."""
    c = config
    t = max_seq or c.max_seq
    pps = pages_per_slot(t, page_tokens)
    pool_pages = batch * pps + 1 if total_pages is None \
        else int(total_pages)
    if pool_pages < pps + 1:
        raise ValueError(
            f"kv_pages={pool_pages}: the pool must hold at least one "
            f"full slot plus the trash page ({pps + 1})")
    table = jnp.zeros((batch, pps), dtype=jnp.int32)
    latent_width = getattr(c, "latent_width", None)
    if latent_width is not None:
        return {"latent": jnp.zeros(
                    (c.n_layers, pool_pages, latent_width, page_tokens),
                    dtype=jnp.dtype(c.dtype)),
                "page_table": table}
    shape = (getattr(c, "n_paged_layers", c.n_layers), pool_pages,
             page_tokens, c.n_kv_heads * c.head_dim)
    if c.kv_dtype == "int8":
        def side():
            return {"int8": jnp.zeros(shape, dtype=jnp.int8),
                    "scale": jnp.zeros(
                        shape[:-1] + (c.n_kv_heads, 1),
                        dtype=jnp.float32)}
    else:
        def side():
            return jnp.zeros(shape, dtype=jnp.dtype(c.dtype))
    per_slot = {
        name: jnp.zeros(lead + (batch,) + shape, dtype=jnp.dtype(dtype))
        for name, (lead, shape, dtype)
        in getattr(c, "slot_state", {}).items()}
    windowed = getattr(c, "n_window_layers", 0)
    if windowed:
        window_shape = (windowed, batch * pps + 1 if window_pages is None
                        else int(window_pages)) + shape[2:]
        per_slot.update(
            window_k=jnp.zeros(window_shape, dtype=jnp.dtype(c.dtype)),
            window_v=jnp.zeros(window_shape, dtype=jnp.dtype(c.dtype)),
            window_table=jnp.zeros((batch, pps), dtype=jnp.int32))
    return {"k": side(), "v": side(), "page_table": table, **per_slot}


def is_paged(cache) -> bool:
    return isinstance(cache, dict) and "page_table" in cache


def _payload(layer):
    return layer["int8"] if is_quantized(layer) else layer


def pool_page_tokens(cache: dict) -> int:
    """Static tokens-per-page of a paged cache's pool."""
    if "latent" in cache:
        return cache["latent"].shape[3]
    return _payload(cache["k"]).shape[2]


def paged_extent(cache: dict) -> int:
    """Logical per-slot extent (== max_seq) of a paged cache."""
    return cache["page_table"].shape[1] * pool_page_tokens(cache)


def _gather(arr, table):
    """``[P, pt, ...]`` pool -> logical rows via an index-array gather:
    table [B, pps] -> [B, pps*pt, ...]; table [pps] -> [pps*pt, ...].
    Contiguous-minor reshape after the gather, so the result matches
    the dense cache's flat row layout exactly."""
    rows = arr[table]
    lead = table.shape[:-1]
    return rows.reshape(*lead, -1, *arr.shape[2:])


def gather_layer(layer, table):
    """One pool layer (payload or int8 dict) -> the dense flat layer
    view ``[B, T, ...]`` the attention consumers expect."""
    if is_quantized(layer):
        return {"int8": _gather(layer["int8"], table),
                "scale": _gather(layer["scale"], table)}
    return _gather(layer, table)


#: The v5e compiler splits a gather whose slice is larger than 512 KiB
#: into "mini-gathers" over halves of the operand's minor axis -- and
#: materialises each half of the WHOLE pool to do it (two 3.1 GB copies
#: a chunk at [3, 1057, 128, 3840] bf16, found by compiling the real
#: shapes for the described chip: PERF.md, PR 33).
_GATHER_SLICE_BYTES = 480 << 10


def _gather_parts(arr) -> int:
    """Into how many part-pages (a power of two, whole 16-row tiles) a
    page of pool ``arr [L, P, pt, ...]`` is gathered so that one slice
    stays under :data:`_GATHER_SLICE_BYTES`: 1 for every pool whose page
    already is (1024-wide bf16 pages of 128 tokens are 256 KiB)."""
    page_tokens = arr.shape[2]
    page_bytes = arr.dtype.itemsize * math.prod(arr.shape[2:])
    parts = 1
    while page_bytes // parts > _GATHER_SLICE_BYTES \
            and page_tokens % (32 * parts) == 0:
        parts *= 2
    return parts


def gather_rows(side, table, index):
    """Layer ``index`` (may be traced) of a STACKED pool side
    ``[L, P, pt, ...]`` -> the logical rows ``[N, T, ...]`` of table
    rows ``[N, pps]``: one gather that reads only those pages, so no
    per-layer slice of the pool materialises ahead of it (admission's
    in-scan read; the pool is closed over, never a scan input)."""
    def take(arr):
        parts = _gather_parts(arr)
        if parts == 1:
            rows = arr[index, table]           # [N, pps, pt, ...]
        else:
            # part-pages: the same bytes, each slice under the size
            # past which the compiler splits the OPERAND
            split = arr.reshape(arr.shape[0], -1, arr.shape[2] // parts,
                                *arr.shape[3:])
            rows = split[index, (table[..., None] * parts
                                 + jnp.arange(parts)).reshape(
                                     table.shape[0], -1)]
        return rows.reshape(table.shape[0], -1, *arr.shape[3:])
    if is_quantized(side):
        return {"int8": take(side["int8"]), "scale": take(side["scale"])}
    return take(side)


def scatter_pages(old, new, table, slots, starts, page_tokens: int):
    """Write whole-page prefill chunks through the page table, all
    layers at once: one ``dynamic_update_slice`` per (row, covered
    page).  ``old`` is one STACKED pool array ``[L, P, pt, ...]``
    (donated: updated in place), ``new`` the page-aligned chunks
    ``[L, N, S, ...]`` the layer scan emitted (S a whole number of
    pages), ``slots``/``starts`` index ``new``'s rows into the table
    (scalars may be traced; the row/page unroll is static).
    Duplicated bucket-pad rows rewrite the same physical pages with the
    same values.  The page-granular twin of
    ``llama._scatter_positions``; the pool is touched nowhere else."""
    n, s = new.shape[1], new.shape[2]
    for i in range(n):
        for j in range(s // page_tokens):
            page = table[slots[i], starts[i] // page_tokens + j]
            part = jax.lax.dynamic_slice(
                new, (0, i, j * page_tokens) + (0,) * (new.ndim - 3),
                (new.shape[0], 1, page_tokens) + new.shape[3:])
            old = jax.lax.dynamic_update_slice(
                old, part, (0, page, 0) + (0,) * (old.ndim - 3))
    return old


def gather_latent_pages(pool, table, index):
    """Layer ``index`` (may be traced) of a latent pool ``[L, P, W, pt]``
    -> the pages ``[N, pps, W, pt]`` of table rows ``[N, pps]``: one
    gather that reads only those pages (the pool is closed over, never
    a scan input).  The logical row ``t`` of a slot is ``[.., t // pt,
    :, t % pt]``; consumers contract over the page and token axes as
    they lie, so no row view is materialised."""
    return pool[index, table]


def latent_pages(rows, page_tokens: int):
    """Rows ``[..., S, W]`` (S whole pages) -> pages ``[..., S // pt, W,
    pt]``, the pool's own form."""
    *lead, s, w = rows.shape
    return jnp.swapaxes(
        rows.reshape(*lead, s // page_tokens, page_tokens, w), -1, -2)


def scatter_latent_pages(old, new, table, slots, starts):
    """Write whole-page prefill chunks into a latent pool ``[L, P, W,
    pt]`` (donated: updated in place) through the page table, all
    layers at once: ``new`` ``[L, N, pages, W, pt]`` (``latent_pages``
    of the chunks the layer loop emitted), row ``i`` starting at token
    ``starts[i]`` of slot ``slots[i]``.  One ``dynamic_update_slice``
    per (row, page), as :func:`scatter_pages`."""
    page_tokens = old.shape[3]
    for i in range(new.shape[1]):
        for j in range(new.shape[2]):
            page = table[slots[i], starts[i] // page_tokens + j]
            old = jax.lax.dynamic_update_slice(
                old, new[:, i, j][:, None], (0, page, 0, 0))
    return old


def scatter_latent_rows(old, new, table, positions):
    """Write one token's row per batch row into a latent pool ``[L, P,
    W, pt]`` (donated) through the page table, all layers at once:
    ``new`` ``[L, B, W]``, row ``b`` landing at logical position
    ``positions[b]`` of its slot.  One unrolled
    ``dynamic_update_slice`` per batch row (a batched scatter defeats
    the in-place aliasing: ``llama._scatter_positions``, whose
    single-array twin this is)."""
    page_tokens = old.shape[3]
    for row in range(new.shape[1]):
        position = positions[row]
        old = jax.lax.dynamic_update_slice(
            old, new[:, row][:, None, :, None],
            (0, table[row, position // page_tokens], 0,
             position % page_tokens))
    return old


def gather_slot(layer, table_row):
    """One slot's pages -> its contiguous ``[1, T, ...]`` row view."""
    if is_quantized(layer):
        return {"int8": _gather(layer["int8"], table_row)[None],
                "scale": _gather(layer["scale"], table_row)[None]}
    return _gather(layer, table_row)[None]


_PREFIX_SEED = 0x9E3779B97F4A7C15


def prefix_page_keys(tokens, page_tokens: int, limit: int | None = None):
    """Rolling prefix-hash chain for ``tokens``: one key per WHOLE page
    the sequence covers, each key a function of every token up to and
    including that page (so two chains agree exactly on their common
    prefix of identical pages).  ``limit`` caps the number of keys."""
    pt = int(page_tokens)
    pages = len(tokens) // pt
    if limit is not None:
        pages = min(pages, int(limit))
    keys, h = [], _PREFIX_SEED
    for p in range(pages):
        h = hash((h, tuple(tokens[p * pt:(p + 1) * pt])))
        keys.append(h)
    return keys


class PageAllocator:
    """Host-side free list + per-slot page assignments.  Owned by the
    ContinuousBatcher (single-threaded with its step loop); the device
    page table is updated from :attr:`dirty` rows folded into the next
    dispatch, so allocation never costs a device round trip of its
    own.

    Prefix cache (ISSUE 18, ``prefix_cache=True``): prompt-covering
    pages are additionally keyed by a rolling prefix hash of the tokens
    they hold (:func:`prefix_page_keys`).  A later request whose prompt
    starts with the same page chain ADOPTS those physical pages
    read-only -- its table row points at the donor's pages and its
    prefill starts past the shared span.  Correctness rests on KV
    position-determinism: K/V at position ``i`` are a pure function of
    ``(token_i, i)``, so identical tokens at identical positions yield
    byte-identical pages, and a clamped admission chunk re-scattering a
    shared page rewrites it with the very same bytes.  Sharing is
    refcounted per physical page (mapping slots + 1 while indexed);
    "copy-on-write at the first divergent page" means the divergent
    page is simply never mapped -- the adopter allocates a fresh page
    there and prefills it, leaving the donor untouched.  The index
    itself holds a reference, so warm pages survive their slot and
    serve the next request; under pool pressure :meth:`ensure` reclaims
    index-only (refcount-1) entries leaf-first.

    Window pages (``window_pages`` > 0: the physical pages of a cache's
    ``window_k`` / ``window_v`` pools, ``init_paged_cache``): a second
    free list and table row a slot, ``window_dirty`` beside ``dirty``.
    :meth:`ensure` with ``first`` covers logical pages [first, pages)
    there and gives back every page of the slot before ``first`` -- the
    caller names as ``first`` the page of the lowest position a program
    not yet retired may still read (``ContinuousBatcher._ensure_pages``),
    so a page goes back only once no program in flight can read it, and
    a page another slot takes is written by a program launched after
    every one that read it (the device runs them in launch order)."""

    def __init__(self, total_pages: int, pages_per_slot: int,
                 max_slots: int, prefix_cache: bool = False,
                 prefix_min_tokens: int = 64, window_pages: int = 0):
        self.total = int(total_pages)
        self.pps = int(pages_per_slot)
        self.max_slots = int(max_slots)
        # Page 0 is the reserved trash page; ascending hand-out order
        # keeps tests deterministic.
        self._free = list(range(self.total - 1, 0, -1))
        self._slots: dict[int, dict[int, int]] = {}
        # slot -> host table row pending upload (numpy-friendly lists).
        self.dirty: dict[int, list[int]] = {}
        # -- window pages ------------------------------------------------
        self.window_total = int(window_pages)
        self._window_free = list(range(self.window_total - 1, 0, -1))
        self._window_slots: dict[int, dict[int, int]] = {}
        self.window_dirty: dict[int, list[int]] = {}
        self.window_released = 0       # pages given back behind a window
        # -- prefix cache ------------------------------------------------
        self.prefix_cache = bool(prefix_cache)
        self.prefix_min_tokens = int(prefix_min_tokens)
        # phys page -> holders (mapping slots, +1 while in the index).
        self._refs: dict[int, int] = {}
        # prefix key -> phys page, insertion order == LRU order (hits
        # and registrations re-insert).  _key_of inverts it for
        # release-time decref; _children drives leaf-first reclaim.
        self._prefix: dict[int, int] = {}
        self._key_of: dict[int, int] = {}
        self._parent: dict[int, int | None] = {}
        self._children: dict[int, int] = {}
        # hit accounting for telemetry/bench (host-side, resettable).
        self.prefix_hits = 0            # pages adopted from the index
        self.prefix_lookups = 0         # whole prompt pages looked up

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def pages_for(self, tokens: int, page_tokens: int) -> int:
        return min(self.pps,
                   -(-max(0, int(tokens)) // int(page_tokens)))

    def holds(self, slot: int) -> int:
        return len(self._slots.get(slot, ()))

    def window_holds(self, slot: int) -> int:
        """Window pages the slot holds."""
        return len(self._window_slots.get(slot, ()))

    def missing(self, slot: int, pages: int) -> int:
        """How many NEW pages covering logical pages [0, pages) would
        need allocating for ``slot``."""
        owned = self._slots.get(slot, {})
        return sum(1 for logical in range(min(pages, self.pps))
                   if logical not in owned)

    def ensure(self, slot: int, pages: int, first: int = 0) -> bool:
        """Allocate (atomically) whatever logical pages [0, pages) the
        slot is missing -- and, with window pages, whatever window pages
        [first, pages) it is missing, once those before ``first`` are
        given back.  False (and no change) when a free list cannot
        cover them -- after reclaiming unreferenced prefix-index
        entries leaf-first when the cache is on."""
        pages = min(int(pages), self.pps)
        owned = self._slots.setdefault(slot, {})
        wanted = [logical for logical in range(pages)
                  if logical not in owned]
        if len(wanted) > len(self._free):
            self._reclaim(len(wanted) - len(self._free))
        if len(wanted) > len(self._free):
            return False
        if self.window_total:
            window = self._window_slots.setdefault(slot, {})
            behind = [logical for logical in window if logical < first]
            wanted_window = [logical for logical in range(first, pages)
                             if logical not in window]
            if len(wanted_window) > len(self._window_free) + len(behind):
                return False
            if behind or wanted_window:
                row = self.window_dirty.setdefault(
                    slot, self._row(slot, window))
                for logical in behind:
                    self._window_free.append(window.pop(logical))
                    row[logical] = 0
                self.window_released += len(behind)
                for logical in wanted_window:
                    phys = self._window_free.pop()
                    window[logical] = phys
                    row[logical] = phys
        if wanted:
            row = self.dirty.setdefault(slot, self._row(slot))
            for logical in wanted:
                phys = self._free.pop()
                owned[logical] = phys
                row[logical] = phys
        return True

    def release(self, slot: int) -> int:
        """Drop the slot's claim on every page it holds (slot finish,
        cancel, eviction) and mark its table row for reset.  Pages the
        prefix index (or another adopter) still references stay
        allocated; the rest return to the free list."""
        owned = self._slots.pop(slot, {})
        if not owned:
            return 0
        freed = []
        for phys in owned.values():
            refs = self._refs.get(phys, 1) - 1
            if refs <= 0:
                self._refs.pop(phys, None)
                self._unindex(phys)
                freed.append(phys)
            else:
                self._refs[phys] = refs
        self._free.extend(sorted(freed, reverse=True))
        self.dirty[slot] = [0] * self.pps
        window = self._window_slots.pop(slot, {})
        if window:
            self._window_free.extend(window.values())
            self.window_dirty[slot] = [0] * self.pps
        return len(owned)

    def reset(self) -> None:
        """Forget everything (device state was rebuilt).  The prefix
        index goes too: recover/failover re-initialized the pool, so
        cached page CONTENT no longer exists -- the cache restarts
        cold."""
        self._free = list(range(self.total - 1, 0, -1))
        self._slots.clear()
        self.dirty.clear()
        self._window_free = list(range(self.window_total - 1, 0, -1))
        self._window_slots.clear()
        self.window_dirty.clear()
        self._refs.clear()
        self._prefix.clear()
        self._key_of.clear()
        self._parent.clear()
        self._children.clear()

    # -- prefix cache ------------------------------------------------------

    def match_prefix(self, tokens, page_tokens: int) -> int:
        """How many leading WHOLE pages of ``tokens`` the index can
        supply.  Capped one page short of covering the full prompt:
        at least one token must prefill so the first generated token
        has last-position logits to sample from."""
        if not self.prefix_cache \
                or len(tokens) < self.prefix_min_tokens:
            return 0
        limit = min(self.pps, (len(tokens) - 1) // int(page_tokens))
        matched = 0
        for key in prefix_page_keys(tokens, page_tokens, limit):
            if key not in self._prefix:
                break
            matched += 1
        return matched

    def adopt_prefix(self, slot: int, tokens, page_tokens: int) -> int:
        """Map the longest indexed page chain matching ``tokens`` into
        ``slot`` read-only (refcount +1 per page) and return the token
        count covered -- the span admission skips.  The slot must hold
        no pages yet (fresh admission).  Counts lookups/hits for the
        hit-rate metric whenever the cache is consulted."""
        if not self.prefix_cache \
                or len(tokens) < self.prefix_min_tokens:
            return 0
        pt = int(page_tokens)
        limit = min(self.pps, (len(tokens) - 1) // pt)
        self.prefix_lookups += max(0, limit)
        owned = self._slots.setdefault(slot, {})
        if owned:
            return 0
        row = None
        for logical, key in enumerate(
                prefix_page_keys(tokens, pt, limit)):
            phys = self._prefix.get(key)
            if phys is None:
                break
            if row is None:
                row = self.dirty.setdefault(slot, self._row(slot))
            self._refs[phys] = self._refs.get(phys, 1) + 1
            owned[logical] = phys
            row[logical] = phys
            # LRU bump: re-insert at the MRU end.
            self._prefix.pop(key)
            self._prefix[key] = phys
            self.prefix_hits += 1
        return len(owned) * pt

    def register_prefix(self, slot: int, tokens, upto: int,
                        page_tokens: int) -> None:
        """Index every whole page of ``tokens[:upto]`` the slot holds
        (admission progressed to ``upto``).  Indexing a page takes a
        reference, so the content outlives the slot; already-indexed
        pages (including ones this slot adopted) are left alone -- the
        index keeps ONE canonical physical page per prefix key."""
        if not self.prefix_cache \
                or len(tokens) < self.prefix_min_tokens:
            return
        pt = int(page_tokens)
        owned = self._slots.get(slot, {})
        limit = min(self.pps, max(0, int(upto)) // pt,
                    len(tokens) // pt)
        parent = None
        for logical, key in enumerate(
                prefix_page_keys(tokens, pt, limit)):
            phys = owned.get(logical)
            if phys is None:
                break
            held = self._prefix.get(key)
            if held is None and self._key_of.get(phys) is None:
                self._prefix[key] = phys
                self._key_of[phys] = key
                self._refs[phys] = self._refs.get(phys, 1) + 1
                self._parent[phys] = parent
                if parent is not None:
                    self._children[parent] = \
                        self._children.get(parent, 0) + 1
            elif held is not None:
                # LRU bump for the canonical page of this prefix.
                self._prefix.pop(key)
                self._prefix[key] = held
            canonical = held if held is not None else phys
            parent = canonical

    def _unindex(self, phys: int) -> None:
        """Drop ``phys`` from the prefix index (its content is gone or
        its refcount hit zero)."""
        key = self._key_of.pop(phys, None)
        if key is not None:
            self._prefix.pop(key, None)
        parent = self._parent.pop(phys, None)
        if parent is not None and parent in self._children:
            remaining = self._children[parent] - 1
            if remaining <= 0:
                self._children.pop(parent, None)
            else:
                self._children[parent] = remaining
        self._children.pop(phys, None)

    def _reclaim(self, need: int) -> int:
        """Free up to ``need`` pages held ONLY by the prefix index
        (refcount 1), leaf-first in LRU order, so pool pressure evicts
        the cache before it preempts a live slot."""
        if need <= 0 or not self._prefix:
            return 0
        reclaimed = 0
        progress = True
        while reclaimed < need and progress:
            progress = False
            for key, phys in list(self._prefix.items()):
                if self._refs.get(phys, 0) != 1 \
                        or self._children.get(phys, 0):
                    continue            # mapped by a slot, or a parent
                self._refs.pop(phys, None)
                self._unindex(phys)
                self._free.append(phys)
                reclaimed += 1
                progress = True
                if reclaimed >= need:
                    break
        if reclaimed:
            self._free.sort(reverse=True)
        return reclaimed

    def leaked_pages(self) -> int:
        """Allocated pages no slot maps and the index does not hold --
        0 in a healthy allocator (the zero-leak invariant tests
        assert)."""
        live = set()
        for owned in self._slots.values():
            live.update(owned.values())
        live.update(self._key_of)
        return self.total - 1 - len(self._free) - len(live)

    def _row(self, slot: int, owned: dict | None = None) -> list[int]:
        row = [0] * self.pps
        owned = self._slots.get(slot, {}) if owned is None else owned
        for logical, phys in owned.items():
            row[logical] = phys
        return row

    @property
    def stats(self) -> dict:
        out = {"total": self.total, "free": self.free_pages,
               "held": {slot: len(pages)
                        for slot, pages in self._slots.items()}}
        if self.prefix_cache:
            out["prefix_pages"] = len(self._prefix)
            out["prefix_hits"] = self.prefix_hits
            out["prefix_lookups"] = self.prefix_lookups
        return out
