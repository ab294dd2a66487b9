"""Paged MX KV cache: host-side page pool + device-side cache surgery.

The paper's serving argument is that decode is HBM-bandwidth-bound on the
KV cache, so the cache should be (a) MX-compressed and (b) allocated at the
granularity traffic actually arrives in. This module supplies (b): a global
pool of fixed-size pages (fp8/fp4 element pages + E8M0 scale pages, or
bf16 pages for the baseline), a free-list allocator, and the jit-able
transfer that installs a request's prefill cache into its pages.

Split of responsibilities:

  * ``PagePool`` — pure host bookkeeping (free list, peak-usage stats).
    Which physical page holds which (sequence, position) range is decided
    here; device arrays never carry ownership metadata.
  * ``install_prefill`` — device-side: scatter a single-sequence prefill
    cache (built by ``model.prefill`` with ``serve_full_cache=True``, so
    slot == absolute position and T is a page multiple) into the pools at
    the sequence's page ids, and recurrent state rows into its slot row.
  * byte accounting — the benchmark's cache-bytes/token numbers come from
    the same walk that does the install, so they can't drift from what is
    actually allocated.

The model-level cache pytree (``model.init_paged_cache``) interleaves two
kinds of per-block caches; they are told apart structurally:
  * page pools: dicts with "k"/"v" (wide) or "k_elems"/… (MX) leaves
    shaped (NP, KVH, PS, ·), with a leading num_groups axis inside
    ``cache["groups"]``;
  * recurrent state: any other dict; leaves have the slot axis first
    (again +1 leading group axis inside ``groups``).
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp


def pages_for(num_tokens: int, page_size: int) -> int:
    """Number of pages needed to hold ``num_tokens`` cache rows."""
    return -(-num_tokens // page_size)


def pages_spanned(pos0: int, num_tokens: int, page_size: int) -> int:
    """Pages a write of ``num_tokens`` rows at positions ``pos0..`` needs.

    The speculative-verify write window: a verify step writes the pending
    token plus K drafts at positions ``pos0 .. pos0 + num_tokens - 1``,
    so the sequence's page table must reach page
    ``(pos0 + num_tokens - 1) // page_size`` *before* the step runs (and
    the engine must own every page in the window exclusively — see the
    rollback note below). Returns that page count (table length), i.e.
    ``last_page + 1``.

    Rollback contract (page-exact): rejected drafts are rolled back by
    *truncation only* — the scheduler simply does not advance ``seq.pos``
    past the accepted point. The rejected rows stay in the pages as
    garbage; they are dead to every reader because all attention paths
    mask keys by position (``kpos <= pos``), and the next write at that
    position overwrites them in place. Nothing is zeroed, copied, or
    freed, which is what makes rollback O(1) and COW-safe: because the
    engine copy-on-writes the whole window before the speculative write,
    shared prefix pages (radix tree, other sequences, swapped-out
    holders) are never touched by a write that might be rolled back.
    """
    if num_tokens <= 0:
        raise ValueError("write window must cover at least one token")
    return (pos0 + num_tokens - 1) // page_size + 1


#: Unit cost of a full-width page, in quarter-page units. The tiered
#: mixed-format pool stores every page's elements in full-width uint8 rows
#: (narrower formats occupy a row *prefix*), so the *physical* array is
#: sized for fp8 — but the HBM-budget argument tiers make is about the
#: bytes a page's format actually needs: fp8 = 4/4, fp6 = 3/4, fp4 = 2/4
#: of a full page. ``PagePool`` can meter allocation against that logical
#: budget so repacking pages down the ladder genuinely frees capacity.
PAGE_UNITS_FULL = 4

#: Quarter-page unit cost per element format bit width.
UNITS_BY_BITS = {8: 4, 6: 3, 4: 2}


class PagePool:
    """Ref-counted free-list allocator over a fixed set of physical page ids.

    Any free page can serve any sequence (no fragmentation by design), so
    allocation is O(n) pops and ``alloc`` fails only when the pool is
    genuinely out of pages — the scheduler then evicts prefix-cache leaves
    or preempts.

    Sharing: a physical page can back many sequences' page tables (prompt
    prefix sharing) plus the prefix radix tree. ``alloc`` hands out pages
    with one reference; every additional holder calls :meth:`retain`, every
    holder releases with :meth:`free`, and the page returns to the free
    list only when its last reference drops. Writers must hold the only
    reference (copy-on-write is the engine's job; ``ref`` exposes the count
    so it can tell).

    Tiered budget metering: with ``unit_budget`` set (quarter-page units,
    see :data:`PAGE_UNITS_FULL`), every freshly allocated page is charged
    the full 4 units (new writes always land hot fp8), the tiering engine
    credits units back by calling :meth:`set_cost` when it repacks a page
    to a narrower format, and :meth:`can_alloc`/:meth:`alloc` admit only
    while both physical pages *and* units remain. The physical page count
    should then over-provision the fp8-equivalent budget (the engine uses
    2x) so the pool can hold more, narrower pages than an all-fp8 pool of
    the same byte budget. ``unit_budget=None`` keeps the legacy
    pages-only behavior.
    """

    def __init__(self, num_pages: int, unit_budget: Optional[int] = None,
                 track_allocs: bool = False):
        if num_pages <= 0:
            raise ValueError("num_pages must be positive")
        if unit_budget is not None and unit_budget <= 0:
            raise ValueError("unit_budget must be positive")
        self.num_pages = num_pages
        self.unit_budget = unit_budget
        self.track_allocs = track_allocs
        #: With ``track_allocs``: every page id handed out by :meth:`alloc`
        #: since the last drain. The tiering engine drains this each step to
        #: reset a recycled page's format id back to hot fp8 — a page that
        #: was repacked to fp4, freed, and re-allocated would otherwise keep
        #: its stale narrow format id while new writes land fp8 bytes.
        self.alloc_log: List[int] = []
        self._free: List[int] = list(range(num_pages - 1, -1, -1))
        self._free_set = set(self._free)  # O(1) double-free detection
        self._ref = [0] * num_pages
        self._cost = [PAGE_UNITS_FULL] * num_pages
        self.units_in_use = 0
        self.peak_in_use = 0
        self.peak_units = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def units_free(self) -> Optional[int]:
        """Remaining quarter-page units (None when not metering)."""
        if self.unit_budget is None:
            return None
        return self.unit_budget - self.units_in_use

    def ref(self, pid: int) -> int:
        """Current reference count of ``pid`` (0 = on the free list)."""
        if not 0 <= pid < self.num_pages:
            raise ValueError(f"unknown page {pid}")
        return self._ref[pid]

    def cost(self, pid: int) -> int:
        """Current unit cost of allocated page ``pid``."""
        if not 0 <= pid < self.num_pages:
            raise ValueError(f"unknown page {pid}")
        return self._cost[pid]

    def set_cost(self, pid: int, units: int) -> None:
        """Re-meter an allocated page after a format change (repack).

        The tiering engine calls this when a page's element format flips:
        repack down the ladder credits units back to the budget; promoting
        back to hot (rewrite) charges them again. Refcounts are untouched
        — cost is a property of the physical page, shared by all holders.
        """
        if not 0 <= pid < self.num_pages:
            raise ValueError(f"unknown page {pid}")
        if self._ref[pid] == 0:
            raise ValueError(f"set_cost of free page {pid}")
        if not 1 <= units <= PAGE_UNITS_FULL:
            raise ValueError(f"bad page cost {units}")
        self.units_in_use += units - self._cost[pid]
        self._cost[pid] = units
        self.peak_units = max(self.peak_units, self.units_in_use)

    def can_alloc(self, n: int) -> bool:
        if n > len(self._free):
            return False
        return (self.unit_budget is None or
                self.units_in_use + n * PAGE_UNITS_FULL <= self.unit_budget)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` page ids (refcount 1, full cost), or None (no change)."""
        if n < 0:
            raise ValueError("alloc of negative page count")
        if not self.can_alloc(n):
            return None
        ids = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(ids)
        for pid in ids:
            self._ref[pid] = 1
            self._cost[pid] = PAGE_UNITS_FULL
        if self.track_allocs:
            self.alloc_log.extend(ids)
        self.units_in_use += n * PAGE_UNITS_FULL
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        self.peak_units = max(self.peak_units, self.units_in_use)
        return ids

    def retain(self, ids) -> None:
        """Add one reference to each allocated page in ``ids``."""
        for pid in ids:
            if not 0 <= pid < self.num_pages:
                raise ValueError(f"retain of unknown page {pid}")
            if self._ref[pid] == 0:
                raise ValueError(f"retain of free page {pid}")
            self._ref[pid] += 1

    def free(self, ids) -> None:
        """Drop one reference per page; last reference frees the page."""
        for pid in ids:
            if not 0 <= pid < self.num_pages:
                raise ValueError(f"free of unknown page {pid}")
            if pid in self._free_set or self._ref[pid] == 0:
                raise ValueError(f"double free of page {pid}")
            self._ref[pid] -= 1
            if self._ref[pid] == 0:
                self.units_in_use -= self._cost[pid]
                self._free.append(pid)
                self._free_set.add(pid)


# ---------------------------------------------------------------------------
# structural walk over the model cache pytree
# ---------------------------------------------------------------------------

_POOL_KEYS = ({"k", "v"}, {"k_elems", "k_scales", "v_elems", "v_scales"})


def _is_pool(block_cache) -> bool:
    return isinstance(block_cache, dict) and set(block_cache) in _POOL_KEYS


def _iter_blocks(cache):
    """Yield (key_path, block_cache, grouped) for every block's cache."""
    for key, val in cache.items():
        if key == "groups":
            for i, blk in enumerate(val):
                yield (key, i), blk, True
        else:
            yield (key,), val, False


def _set_block(cache, path, new_blk):
    cache = dict(cache)
    if path[0] == "groups":
        groups = list(cache["groups"])
        groups[path[1]] = new_blk
        cache["groups"] = tuple(groups)
    else:
        cache[path[0]] = new_blk
    return cache


def _install_pool(pool, contig, page_ids, page_size, grouped):
    """Scatter a (1, T, KVH, ·) contiguous cache into KV-head-major pool
    pages ``page_ids``."""
    n = page_ids.shape[0]
    new = {}
    for key in pool:
        src = contig[key]
        if grouped:
            g = src.shape[0]
            pages = src.reshape(g, n, page_size, *src.shape[3:])
            new[key] = pool[key].at[:, page_ids].set(pages.swapaxes(2, 3))
        else:
            pages = src.reshape(n, page_size, *src.shape[2:])
            new[key] = pool[key].at[page_ids].set(pages.swapaxes(1, 2))
    return new


def _install_state(state, contig, slot, grouped):
    """Write a batch-1 recurrent state into the pool's ``slot`` row."""
    if grouped:
        return jax.tree_util.tree_map(
            lambda pool, src: pool.at[:, slot].set(src[:, 0]), state, contig)
    return jax.tree_util.tree_map(
        lambda pool, src: pool.at[slot].set(src[0]), state, contig)


def install_prefill(cache, prefill_cache, slot, page_ids, page_size: int):
    """Install one request's prefill cache into the paged model cache.

    ``prefill_cache`` comes from ``model.prefill`` on a batch of 1 with
    ``serve_full_cache=True`` and ``max_seq == len(page_ids) * page_size``
    (so its T dim factors exactly into the allocated pages). ``slot`` is
    the request's decode-batch row; recurrent state lands there. Returns
    the updated cache pytree (jit-able; retraces per page count).
    """
    for path, blk, grouped in _iter_blocks(cache):
        src = prefill_cache[path[0]] if len(path) == 1 else \
            prefill_cache["groups"][path[1]]
        if _is_pool(blk):
            src = {key: src[key] for key in blk}  # drop kpos
            blk = _install_pool(blk, src, page_ids, page_size, grouped)
        else:
            blk = _install_state(blk, src, slot, grouped)
        cache = _set_block(cache, path, blk)
    return cache


def install_prefill_offset(cache, prefill_cache, slot, page_ids,
                           page_size: int, offset: int, num_rows: int):
    """Install a prefill *tail* starting at a non-page-aligned position.

    The partial-page prefix-hit path: a prefix-cache hit may end mid-page
    (``offset = cached % page_size != 0``), so the freshly prefillled tail
    rows land at row ``offset`` of the first page in its write window
    rather than at a page boundary. ``prefill_cache`` covers the tail only
    (row r is absolute position ``offset + r`` within ``page_ids``'
    span); only the first ``num_rows`` rows are live, the rest padding.
    The engine must own every written page exclusively (COW first) — the
    partial hit page keeps its cached prefix rows and receives the tail
    rows in place. Recurrent state rows install whole, as in
    :func:`install_prefill` (sharing implies attention-only models, so
    state blocks are empty on this path anyway). jit-able; retraces per
    (pages, offset, num_rows).
    """
    rows = jnp.arange(num_rows, dtype=jnp.int32) + offset
    pidx = page_ids[rows // page_size]
    sidx = rows % page_size
    for path, blk, grouped in _iter_blocks(cache):
        src = prefill_cache[path[0]] if len(path) == 1 else \
            prefill_cache["groups"][path[1]]
        if _is_pool(blk):
            # pools are (.., NP, KVH, PS, ·): [pidx, :, sidx] addresses
            # (rows, KVH, ·); with the group axis in front, the indexed
            # dims lead the result, so rows go first there too
            if grouped:
                blk = {key: blk[key].at[:, pidx, :, sidx].set(
                    src[key][:, 0, :num_rows].swapaxes(0, 1))
                    for key in blk}
            else:
                blk = {key: blk[key].at[pidx, :, sidx].set(
                    src[key][0, :num_rows]) for key in blk}
        else:
            blk = _install_state(blk, src, slot, grouped)
        cache = _set_block(cache, path, blk)
    return cache


def copy_page(cache, src, dst):
    """Copy one physical page's contents ``src`` -> ``dst`` in every pool.

    The device half of copy-on-write: when a sequence must write into a
    page other holders reference, the engine allocates a fresh page, copies
    the shared page's bytes here, and repoints the sequence's page table
    before the write. Recurrent state blocks are untouched (they are
    per-slot, never shared). jit-able; ``src``/``dst`` are scalar int32.
    """
    for path, blk, grouped in _iter_blocks(cache):
        if not _is_pool(blk):
            continue
        blk = {key: (leaf.at[:, dst].set(leaf[:, src]) if grouped
                     else leaf.at[dst].set(leaf[src]))
               for key, leaf in blk.items()}
        cache = _set_block(cache, path, blk)
    return cache


# ---------------------------------------------------------------------------
# swap-out / swap-in (exact preemption)
# ---------------------------------------------------------------------------


def extract_seq(cache, slot, page_ids):
    """Snapshot one sequence's cache: its pool pages + its state row.

    Used on preemption: unlike recompute-style preemption, restoring the
    exact cache bytes keeps generation bit-identical — a re-*prefill*
    would attend over unquantized K/V where the original decode attended
    over the MX cache, and the token stream could diverge.

    Returns a pytree mirroring ``cache`` with pool leaves gathered to
    (n_pages, KVH, PS, ·) (grouped: (G, n_pages, KVH, PS, ·)) and state
    leaves sliced to the slot row.
    """
    out = {}
    for path, blk, grouped in _iter_blocks(cache):
        if _is_pool(blk):
            snap = {key: (leaf[:, page_ids] if grouped else leaf[page_ids])
                    for key, leaf in blk.items()}
        else:
            snap = jax.tree_util.tree_map(
                lambda leaf: leaf[:, slot] if grouped else leaf[slot], blk)
        if path[0] == "groups":
            out.setdefault("groups", {})[path[1]] = snap
        else:
            out[path[0]] = snap
    if "groups" in out:
        out["groups"] = tuple(out["groups"][i]
                              for i in range(len(out["groups"])))
    return out


def merge_snapshots(a, b):
    """Concatenate two :func:`extract_seq` snapshots along the page axis.

    Used when a swapped-out request's retained *shared* pages must be
    reclaimed (last-resort pool pressure): their bytes are extracted into
    a second snapshot and appended to the swap's original one, in the
    same order the page indices are appended to its owned list. Only pool
    leaves are merged; state rows keep ``a``'s (sharing implies an
    attention-only model, so state blocks are empty anyway). ``a`` may be
    None (a swap that owned no pages exclusively).
    """
    if a is None:
        return b
    merged = a
    for path, blk, grouped in _iter_blocks(a):
        if not _is_pool(blk):
            continue
        other = b[path[0]] if len(path) == 1 else b["groups"][path[1]]
        blk = {key: jnp.concatenate([leaf, other[key]],
                                    axis=1 if grouped else 0)
               for key, leaf in blk.items()}
        merged = _set_block(merged, path, blk)
    return merged


def restore_seq(cache, snapshot, slot, page_ids):
    """Inverse of :func:`extract_seq` onto freshly allocated pages/slot."""
    for path, blk, grouped in _iter_blocks(cache):
        snap = snapshot[path[0]] if len(path) == 1 else \
            snapshot["groups"][path[1]]
        if _is_pool(blk):
            blk = {key: (leaf.at[:, page_ids].set(snap[key]) if grouped
                         else leaf.at[page_ids].set(snap[key]))
                   for key, leaf in blk.items()}
        else:
            blk = jax.tree_util.tree_map(
                lambda leaf, src: (leaf.at[:, slot].set(src) if grouped
                                   else leaf.at[slot].set(src)), blk, snap)
        cache = _set_block(cache, path, blk)
    return cache


# ---------------------------------------------------------------------------
# sharded pools (KV-head-parallel serve step)
# ---------------------------------------------------------------------------


def pool_specs(cache, axis: str):
    """PartitionSpec pytree sharding every pool leaf's KV-head axis.

    The sharded serve engine partitions each attention layer's page pool
    along its KV-head dimension — layout ``(NP, KVH, PS, ·)``, grouped
    ``(G, NP, KVH, PS, ·)``, so the KV-head axis is always ``ndim - 3``.
    The megakernel's stacked-layer pool (``model.init_megakernel_cache``)
    is the grouped layout with ``G == num_layers``, so these specs — and
    every other structural walk in this module (copy_page,
    extract/restore, repack) — apply to it unchanged; that layout
    coincidence is load-bearing (see ``blocks.megakernel_reject_reason``)
    and is what the sharded-megakernel ROADMAP rung builds on.
    The page axis stays unsharded: every device holds pages
    ``0..NP`` for *its* head slice, so the host page table is replicated
    metadata and extract/restore/copy_page stay shard-local gathers
    under GSPMD. Recurrent state blocks (and anything else that is not a
    pool) are replicated. Returns a tree with the same structure as
    ``cache`` whose leaves are ``PartitionSpec``s — usable both as
    ``shard_map`` in/out specs and (through ``NamedSharding``) as
    ``device_put`` targets.
    """
    from jax.sharding import PartitionSpec as P

    specs = cache
    for path, blk, _grouped in _iter_blocks(cache):
        if _is_pool(blk):
            # no trailing None past the sharded axis: jit hashes the
            # canonical (trimmed) form the step's outputs come back
            # with, and a P(..., axis, None) _shard_put placement would
            # make the first call a second trace
            new = {key: P(*([None] * (leaf.ndim - 3)), axis)
                   for key, leaf in blk.items()}
        else:
            new = jax.tree_util.tree_map(lambda leaf: P(), blk)
        specs = _set_block(specs, path, new)
    return specs


# ---------------------------------------------------------------------------
# byte accounting (benchmark: cache bytes per resident token)
# ---------------------------------------------------------------------------


def cache_nbytes(cache) -> int:
    """Total bytes of every cache leaf (pools + recurrent state)."""
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(cache))


def pool_page_nbytes(cache, num_pages: int) -> int:
    """Bytes one page costs across all attention layers (incl. groups)."""
    total = 0
    for _, blk, _ in _iter_blocks(cache):
        if _is_pool(blk):
            total += sum(leaf.nbytes for leaf in blk.values())
    if total % num_pages:
        raise ValueError("pool bytes not divisible by page count")
    return total // num_pages


def state_nbytes(cache) -> int:
    """Bytes of per-slot recurrent state (not paged)."""
    total = 0
    for _, blk, _ in _iter_blocks(cache):
        if not _is_pool(blk):
            total += sum(leaf.nbytes
                         for leaf in jax.tree_util.tree_leaves(blk))
    return total
