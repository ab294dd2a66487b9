"""Continuous-batching scheduler: admission, prefix sharing, preemption.

Host-side control plane of the serving engine. The device sees only fixed
shapes — (max_slots, 1) token batches and a (max_slots, pages_per_slot)
page table — while requests enter and leave mid-stream:

  * **admission** — FCFS with a bounded skip-ahead window: the queue head
    is admitted as soon as a slot is free and its pages fit; when the head
    does *not* fit, up to ``admit_window - 1`` younger requests are
    scanned for one that does (head-of-line order is preserved otherwise,
    so the window bounds how far fairness can bend).
  * **prefix sharing** — with a :class:`~.prefix_cache.PrefixCache`
    attached, admission first takes the longest page-aligned prefix hit:
    matched pages are retained (ref-counted) into the request's page
    table and only the uncached tail is prefilled. Fresh full prompt
    pages are inserted back into the radix tree after install.
  * **chunked prefill** — with ``prefill_chunk`` set, admission only binds
    the slot and pages (the prompt's worth, exactly as monolithic) and
    marks the sequence ``prefill_pos = cached_tokens``; the engine then
    streams the prompt through fixed-size page-aligned chunks under a
    per-step token budget, interleaved with decode steps (Sarathi-style),
    so resident decoders never stall behind a long prompt and admission
    latency is O(chunk). ``assemble`` skips prefilling sequences — they
    have no pending token until the final chunk's logits are sampled.
    Preempting a mid-prefill sequence is legal: the swap tuple carries
    ``prefill_pos`` and re-admission resumes chunking where it stopped.
    Because admission is decoupled from prefill, a request sharing an
    unregistered page-aligned head with a still-prefilling sequence is
    *deferred* (``deferred_admissions``) until those pages register in
    the prefix tree — otherwise a shared-prefix burst would race past
    the tree and prefill private copies of the same pages.
  * **decode paging** — each step, a slot crossing a page boundary pulls a
    fresh page from the pool. A dry pool first evicts LRU unreferenced
    prefix-tree leaves; if still dry, the *youngest* other active request
    is preempted: the engine snapshots the exact bytes of the pages it
    exclusively owns (shared prefix pages are released by reference and
    never extracted — other holders keep them resident), its references
    are dropped, and it is requeued at the front; re-admission restores
    the snapshot verbatim into fresh pages and re-links the shared ones
    (swap-style preemption). Recompute-style preemption would NOT be
    token-identical here: a re-prefill of *generated* tokens would attend
    over unquantized K/V where the original decode attended over the MX
    cache.
  * **speculative verify windows** — with speculative decoding enabled
    the engine writes 1 + K tokens per step, so ``try_grow`` covers the
    whole window (possibly several fresh pages at once) and ``submit``
    rejects requests whose worst-case window would overflow the page
    table near max_seq (a silent clamp would drop speculated K/V writes
    mid-verify). Rollback of rejected drafts is position truncation
    only — ``advance`` is simply called once per *accepted* token.
  * **recycling** — EOS or max_new_tokens frees the slot and drops the
    sequence's page references in O(1); pages the prefix tree still
    references stay resident as cache, everything else returns to the
    free list, and the next queued request can be admitted the same step.

The scheduler never touches device memory: it hands the engine (slot,
request, page_ids) admission tuples and assembles per-step numpy batches.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, List, Optional

import numpy as np

from .kv_cache import PagePool, pages_for, pages_spanned
from .prefix_cache import PrefixCache


def _common_pages(a: np.ndarray, b: np.ndarray, page_size: int) -> int:
    """Whole pages of identical leading tokens between two prompts."""
    n = min(len(a), len(b))
    diff = np.flatnonzero(a[:n] != b[:n])
    common = int(diff[0]) if len(diff) else n
    return common // page_size


@dataclasses.dataclass
class Request:
    """One generation request. ``generated`` and ``swap`` survive preemption."""

    id: int
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    generated: List[int] = dataclasses.field(default_factory=list)
    # per-request sampling: a serve.sampling.SamplingParams (None = the
    # engine's defaults) plus the resolved uint32 RNG seed — carried on
    # the request so its token stream survives preemption and swap
    # (sampling keys are (seed, token index), never slot or step)
    sampling: Optional[object] = None
    seed: int = 0
    # cancel(): the request was abandoned by its client; its pages and
    # slot are already released and it will never reach ``finished``
    cancelled: bool = False
    # preemption snapshot: (cache_snapshot, owned_idx, pages, resident
    # tokens, cached_tokens, prefill_pos). ``owned_idx`` are the
    # page-table positions that were exclusively owned (extracted +
    # freed); the remaining entries of ``pages`` stayed retained (shared)
    # across the swap. ``prefill_pos`` is the chunked-prefill resume
    # point (None once prefill completed). Restored verbatim on
    # re-admission so generation stays bit-identical.
    swap: Optional[tuple] = None
    # chunked admission deferred this request at least once (the stat
    # counts requests, not retries — admit_next re-tries every step)
    deferred: bool = False
    # how many admission attempts deferral has already cost this request;
    # bounded by Scheduler.max_deferrals so a preempted / stalled leader
    # can't starve it forever (it then prefills independently)
    defer_count: int = 0
    # ``time.perf_counter`` seconds: when the request arrived (its
    # caller's receipt time, else submit()), when it first got a slot,
    # and when its first token was sampled (None until then)
    arrival: float = 0.0
    admitted: Optional[float] = None
    first_token: Optional[float] = None

    @property
    def remaining(self) -> int:
        return self.max_new_tokens - len(self.generated)

    @property
    def done(self) -> bool:
        return self.remaining <= 0


@dataclasses.dataclass
class ActiveSeq:
    """A request bound to a decode slot."""

    req: Request
    slot: int
    pos: int  # next cache write position == tokens currently resident
    pages: List[int]
    order: int  # admission sequence number (preemption picks the youngest)
    cached_tokens: int = 0  # page-aligned prefix-cache hit at admission
    # chunked prefill: next chunk's start row (a multiple of the chunk
    # length past ``cached_tokens``); None once the prompt is fully
    # resident and the sequence decodes. While set, the sequence owns a
    # slot but is skipped by assemble() — it has no pending token yet.
    prefill_pos: Optional[int] = None


class Scheduler:
    def __init__(self, *, max_slots: int, num_pages: int, page_size: int,
                 max_seq: int, prefix_cache: bool = False,
                 admit_window: int = 4, num_draft_tokens: int = 0,
                 prefill_chunk: int = 0, max_deferrals: int = 8,
                 prefill_max_chunks: int = 1,
                 unit_budget: Optional[int] = None,
                 track_allocs: bool = False):
        self.max_slots = max_slots
        self.page_size = page_size
        self.max_seq = max_seq
        # chunked prefill (0 = monolithic): admission only binds the slot
        # and pages; the engine streams the prompt through fixed-size
        # chunks (page-aligned, so every chunk page is wholly owned by
        # one chunk) interleaved with decode steps
        if prefill_chunk and prefill_chunk % page_size != 0:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be a multiple of "
                f"page_size={page_size}: chunk starts must stay "
                "page-aligned so no page blends two chunks")
        self.prefill_chunk = prefill_chunk
        # ragged-aware prefill budgeting: when decode rows undersubscribe
        # the batch (fewer active sequences than slots), a prefilling
        # sequence may take up to this many chunks in one step. Admission
        # bound all of the prompt's pages already, so a bigger bite needs
        # no allocation — only wider (still static) step rows.
        if prefill_max_chunks < 1:
            raise ValueError("prefill_max_chunks must be >= 1")
        self.prefill_max_chunks = prefill_max_chunks
        self.pages_per_slot = pages_for(max_seq, page_size)
        if num_pages < self.pages_per_slot:
            raise ValueError(
                f"num_pages={num_pages} cannot hold one max_seq={max_seq} "
                f"sequence (needs {self.pages_per_slot})")
        if admit_window < 1:
            raise ValueError("admit_window must be >= 1")
        if num_draft_tokens < 0:
            raise ValueError("num_draft_tokens must be >= 0")
        self.admit_window = admit_window
        # speculative decoding: every verify step writes 1 + K tokens, so
        # admission must guarantee the whole worst-case window fits inside
        # max_seq's page table (see submit)
        self.num_draft_tokens = num_draft_tokens
        if max_deferrals < 0:
            raise ValueError("max_deferrals must be >= 0")
        self.max_deferrals = max_deferrals
        self.pool = PagePool(num_pages, unit_budget=unit_budget,
                             track_allocs=track_allocs)
        self.prefix = (PrefixCache(self.pool, page_size)
                       if prefix_cache else None)
        self.queue: deque[Request] = deque()
        self.slots: List[Optional[ActiveSeq]] = [None] * max_slots
        self.finished: List[Request] = []
        self._order = 0
        self._next_id = 0
        # stats sampled at the peak-pages step (benchmark bytes/token)
        self.peak_pages = 0
        self.resident_at_peak = 0
        self.preemptions = 0
        self.skipped_admissions = 0
        self.cow_copies = 0
        self.deferred_admissions = 0  # chunked: waited for a prefix match
        self.deferral_fallbacks = 0  # deferral bound hit: went independent
        self.cancellations = 0
        # streaming hook: called as on_token(request, token, finished)
        # after every recorded token — the async server's per-token
        # delivery path (None = no streaming consumer)
        self.on_token: Optional[Callable] = None

    # -- submission ---------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               sampling=None, seed: int = 0,
               arrival: Optional[float] = None) -> int:
        """Queue one request. Invalid inputs fail here, with a clear
        ValueError, not steps later inside a jitted prefill. ``arrival``
        (``time.perf_counter`` seconds) defaults to now."""
        prompt = np.asarray(prompt)
        if not np.issubdtype(prompt.dtype, np.integer):
            raise ValueError(
                f"prompt must be integer token ids, got dtype {prompt.dtype}")
        prompt = prompt.astype(np.int32).reshape(-1)
        if len(prompt) == 0:
            raise ValueError("empty prompt")
        if not isinstance(max_new_tokens, (int, np.integer)):
            raise ValueError(
                f"max_new_tokens must be an int, got {type(max_new_tokens).__name__}")
        if max_new_tokens <= 0:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if len(prompt) + max_new_tokens > self.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new_tokens}) "
                f"exceeds max_seq={self.max_seq}")
        if (self.num_draft_tokens
                and len(prompt) + max_new_tokens + self.num_draft_tokens
                > self.max_seq):
            # a silent clamp here would let a verify step write speculated
            # K/V past the last page of the table mid-stream — reject at
            # submission with the actual numbers instead
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new ({max_new_tokens}) + "
                f"speculative draft window ({self.num_draft_tokens}) "
                f"exceeds max_seq={self.max_seq}: a verify step near the "
                f"end of this request would overflow its page table "
                f"(shrink num_draft_tokens or raise max_seq)")
        req = Request(self._next_id, prompt, int(max_new_tokens),
                      sampling=sampling, seed=int(seed),
                      arrival=(time.perf_counter() if arrival is None
                               else float(arrival)))
        self._next_id += 1
        self.queue.append(req)
        return req.id

    def cancel(self, request_id: int) -> bool:
        """Abandon a request wherever it currently lives; True if found.

        Active (decoding, mid-prefill, or mid-verify — cancel runs on the
        host between steps, so a verify window is never half-landed):
        drop every page reference in one ``pool.free`` — prefix-cache
        retains and exclusively-owned pages alike; pages the radix tree
        still references stay resident as cache, the rest return to the
        free list — and release the slot the same step. Queued fresh:
        just dequeue (no resources bound yet). Queued swapped-out: the
        preemption already freed the exclusively-owned pages; free the
        *shared* references the swap tuple still pins and drop the
        snapshot. Finished/unknown ids return False (cancel raced
        completion — the tokens already streamed, nothing to release).
        """
        for seq in self.active():
            if seq.req.id == request_id:
                self.pool.free(seq.pages)
                self.slots[seq.slot] = None
                seq.req.cancelled = True
                self.cancellations += 1
                return True
        for qi, req in enumerate(self.queue):
            if req.id != request_id:
                continue
            if req.swap is not None:
                _snapshot, owned_idx, pages, *_ = req.swap
                owned = set(owned_idx)
                shared = [p for i, p in enumerate(pages) if i not in owned]
                if shared:
                    self.pool.free(shared)
                req.swap = None
            del self.queue[qi]
            req.cancelled = True
            self.cancellations += 1
            return True
        return False

    # -- admission / eviction ----------------------------------------------

    def active(self) -> List[ActiveSeq]:
        return [s for s in self.slots if s is not None]

    def prefilling(self) -> List[ActiveSeq]:
        """Active sequences still streaming prompt chunks, oldest first."""
        return sorted((s for s in self.active()
                       if s.prefill_pos is not None),
                      key=lambda s: s.order)

    def decode_ready(self) -> List[ActiveSeq]:
        """Active sequences with a pending token (prefill complete)."""
        return [s for s in self.active() if s.prefill_pos is None]

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    def _alloc_with_evict(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages, evicting prefix-tree leaves if needed.

        Eviction only runs when it can actually cover the shortfall —
        a doomed allocation must not destroy cached prefixes for nothing
        (the caller will retry every step while the request waits).
        """
        if not self.pool.can_alloc(n) and self.prefix is not None:
            shortfall = n - self.pool.free_pages
            if self.prefix.evictable_count() >= shortfall:
                self.prefix.evict(shortfall)
        return self.pool.alloc(n)

    def _try_admit(self, req: Request, slot: int) -> Optional[ActiveSeq]:
        """Bind ``req`` to ``slot`` if its pages fit; None leaves no trace."""
        if req.swap is not None:
            snapshot, owned_idx, pages, pos0, cached, prefill_pos = req.swap
            ids = self._alloc_with_evict(len(owned_idx))
            if ids is None:
                return None
            pages = list(pages)
            for i, pid in zip(owned_idx, ids):
                pages[i] = pid
        else:
            # only fresh requests are prefilled; preempted ones re-enter
            # exclusively via their cache snapshot above (a re-prefill of
            # prompt+generated would not be token-identical: prefill
            # attends over unquantized K/V)
            assert not req.generated, "mid-stream request without snapshot"
            hit, cached = ([], 0)
            if self.prefix is not None:
                # chunked prefill streams page-aligned chunks, so it can
                # only consume page-aligned hits; monolithic admission
                # also takes a partial last-page hit (the engine COWs the
                # partial page and installs the tail rows in place)
                hit, cached = self.prefix.acquire(
                    req.prompt, full_only=bool(self.prefill_chunk))
            if (self.prefill_chunk and self.prefix is not None
                    and req.defer_count < self.max_deferrals):
                # chunked admission is decoupled from prefill, so a burst
                # of shared-prefix prompts could race past the radix tree
                # (monolithic admission registered each prompt's pages
                # before the next request's lookup, making the race
                # impossible). Defer a request whose prompt shares an
                # unregistered page-aligned head with a sequence still
                # streaming chunks: once that sequence registers, this
                # request re-admits with a real tree hit and shares the
                # pages instead of prefilling a private copy. Deferral is
                # bounded (max_deferrals attempts): a leader that stalls —
                # preempted mid-prefill, starved of chunk budget — must
                # not starve this request forever, so past the bound it
                # falls through and prefills independently (correct, just
                # without sharing; dedupe-on-insert may still reconcile
                # the duplicate pages later).
                cap = (len(req.prompt) - 1) // self.page_size
                for s in self.prefilling():
                    shared = min(
                        _common_pages(req.prompt, s.req.prompt,
                                      self.page_size), cap)
                    if shared * self.page_size > cached:
                        if hit:
                            self.pool.free(hit)
                        if not req.deferred:
                            req.deferred = True
                            self.deferred_admissions += 1
                        req.defer_count += 1
                        if req.defer_count == self.max_deferrals:
                            self.deferral_fallbacks += 1
                        return None
            prompt_len = len(req.prompt)
            ids = self._alloc_with_evict(pages_for(prompt_len, self.page_size)
                                         - len(hit))
            if ids is None:
                if hit:
                    self.pool.free(hit)  # drop the lookup's references
                return None
            pages = hit + ids
            if self.prefix is not None:
                self.prefix.record_lookup(cached)
            if self.prefill_chunk:
                # chunked: only the prefix hit is resident so far; the
                # engine streams the tail through fixed chunks, advancing
                # ``pos``/``prefill_pos`` as each chunk's rows land
                pos0, prefill_pos = cached, cached
            else:
                pos0, prefill_pos = prompt_len, None
        seq = ActiveSeq(req=req, slot=slot, pos=pos0, pages=pages,
                        order=self._order, cached_tokens=cached,
                        prefill_pos=prefill_pos)
        self._order += 1
        self.slots[slot] = seq
        return seq

    def admit_next(self) -> Optional[ActiveSeq]:
        """Admit the queue head, or — when it doesn't fit — the first of
        up to ``admit_window - 1`` younger requests that does (bounded
        skip-ahead; strict FCFS otherwise)."""
        free_slots = [i for i, s in enumerate(self.slots) if s is None]
        if not free_slots or not self.queue:
            return None
        for qi in range(min(self.admit_window, len(self.queue))):
            seq = self._try_admit(self.queue[qi], free_slots[0])
            if seq is not None:
                del self.queue[qi]
                if seq.req.admitted is None:
                    seq.req.admitted = time.perf_counter()
                if qi:
                    self.skipped_admissions += 1
                return seq
        return None

    def register_prefix(self, seq: ActiveSeq) -> None:
        """Insert ``seq``'s freshly installed full prompt pages into the
        radix tree (no-op without a prefix cache). Engine calls this after
        the device install, so a later hit always reads real bytes.
        Monolithic prefill also registers the prompt's partial last page
        (chunked can't serve partial hits, so it doesn't pin them)."""
        if self.prefix is not None:
            self.prefix.insert(seq.req.prompt, seq.pages,
                               partial=not self.prefill_chunk)

    def try_grow(self, seq: ActiveSeq, num_tokens: int = 1) -> bool:
        """Grow ``seq``'s page table to cover this step's write window.

        ``num_tokens`` is how many cache rows the step writes starting at
        ``seq.pos`` — 1 for plain decode, 1 + K for a speculative verify
        chunk (which may straddle a page boundary and need several fresh
        pages at once). All-or-nothing: a partial grow would leave the
        window half-backed and the verify write would drop rows silently.
        """
        need = pages_spanned(seq.pos, num_tokens, self.page_size) \
            - len(seq.pages)
        if need <= 0:
            return True
        ids = self._alloc_with_evict(need)
        if ids is None:
            return False
        seq.pages.extend(ids)
        return True

    def pick_victim(self, exclude: ActiveSeq) -> Optional[ActiveSeq]:
        """Youngest other active sequence (FCFS: elders keep their slots)."""
        victims = [s for s in self.active() if s is not exclude]
        return max(victims, key=lambda s: s.order) if victims else None

    def exclusive_pages(self, seq: ActiveSeq):
        """(table indices, page ids) of pages only ``seq`` references —
        the ones a preemption snapshot must extract. Shared pages (prefix
        tree / other sequences) stay resident and are never extracted."""
        idx = [i for i, p in enumerate(seq.pages) if self.pool.ref(p) == 1]
        return idx, [seq.pages[i] for i in idx]

    def preempt(self, victim: ActiveSeq, snapshot,
                owned_idx: Optional[List[int]] = None) -> None:
        """Swap out ``victim``: free its exclusive pages, requeue at front.

        The engine passes the device-side snapshot of the victim's
        exclusively owned pages + state row (``kv_cache.extract_seq``) and
        their table indices; shared pages keep the victim's reference
        across the swap (they cannot be evicted under it). Re-admission
        restores the snapshot verbatim, so preemption never perturbs the
        token stream.
        """
        if owned_idx is None:
            owned_idx = list(range(len(victim.pages)))
        self.pool.free([victim.pages[i] for i in owned_idx])
        self.slots[victim.slot] = None
        victim.req.swap = (snapshot, owned_idx, list(victim.pages),
                           victim.pos, victim.cached_tokens,
                           victim.prefill_pos)
        self.queue.appendleft(victim.req)
        self.preemptions += 1

    def advance(self, seq: ActiveSeq) -> None:
        """The decode step wrote ``seq``'s pending token at ``seq.pos``."""
        seq.pos += 1

    def record_token(self, seq: ActiveSeq, token: int, eos_id=None) -> bool:
        """Append a sampled token; finish + recycle on EOS/max_new.

        ``seq.pos`` is untouched: the token's KV lands in the cache only
        when the next decode step feeds it (see :meth:`advance`). Returns
        True if the sequence is still active.
        """
        seq.req.generated.append(int(token))
        finished = seq.req.done or (eos_id is not None
                                    and int(token) == eos_id)
        if finished:
            self.pool.free(seq.pages)
            self.slots[seq.slot] = None
            self.finished.append(seq.req)
        if self.on_token is not None:
            self.on_token(seq.req, int(token), finished)
        return not finished

    # -- per-step batch assembly -------------------------------------------

    def assemble(self, extra_tokens: int = 0):
        """Fixed-shape numpy batch for the jitted decode/verify step.

        Returns (tokens (NS, 1 + extra_tokens), pos (NS,), page_rows
        (NS, P), active) — inactive rows are token 0 / pos 0 / pages -1
        (their device writes are dropped and their logits ignored).
        Sequences still in chunked prefill are treated as inactive: they
        hold a slot but have no pending token until their final chunk's
        logits are sampled. Column 0 is each slot's pending token; the
        engine fills columns 1.. with its drafter's proposals
        (speculative verify). The shape is static per ``extra_tokens``,
        so the verify step jits once.
        """
        ns, pps = self.max_slots, self.pages_per_slot
        tokens = np.zeros((ns, 1 + extra_tokens), np.int32)
        pos = np.zeros((ns,), np.int32)
        page_rows = np.full((ns, pps), -1, np.int32)
        act = self.decode_ready()
        for seq in act:
            # every activation path records a pending token before the
            # first assemble (admission samples from prefill logits;
            # swapped requests carry theirs in ``generated``)
            assert seq.req.generated, "active sequence with no pending token"
            tokens[seq.slot, 0] = seq.req.generated[-1]
            pos[seq.slot] = seq.pos
            page_rows[seq.slot, : len(seq.pages)] = seq.pages
        # resident rows: decode-ready sequences are about to write their
        # pending token (+1); prefilling ones count what chunks landed
        resident = int(sum(s.pos + (1 if s.prefill_pos is None else 0)
                           for s in self.active()))
        # both stats sampled at the same step: a strict new peak resets the
        # resident count; ties keep the smaller resident (conservative —
        # reports the larger bytes/token)
        if self.pool.pages_in_use > self.peak_pages:
            self.peak_pages = self.pool.pages_in_use
            self.resident_at_peak = resident
        elif self.pool.pages_in_use == self.peak_pages:
            self.resident_at_peak = (resident if self.resident_at_peak == 0
                                     else min(self.resident_at_peak, resident))
        return tokens, pos, page_rows, act

    def prefill_allowed_chunks(self) -> int:
        """How many prefill chunks one sequence may take this step.

        Undersubscribed batches (fewer active sequences than slots —
        tokens the static row width would otherwise waste) let a
        prefilling sequence stream up to ``prefill_max_chunks`` at once;
        a full batch drops back to exactly one chunk, which is the
        starvation bound: decode rows are never displaced, and a
        prefilling sequence always advances >= 1 chunk per step.
        """
        if len(self.active()) < self.max_slots:
            return self.prefill_max_chunks
        return 1

    def planned_prefill_real(self, seq: "ActiveSeq", width: int) -> int:
        """Valid prompt tokens ``seq``'s next ragged chunk will carry.

        Single source of truth for the chunk-size formula: used by
        ``assemble_ragged`` to pack rows and by the tiered engine's
        write-marking pre-pass, which must mark exactly the pages the
        step is about to touch.
        """
        chunk = min(self.prefill_chunk, width) if self.prefill_chunk else 0
        bite = min(chunk * self.prefill_allowed_chunks(), width)
        return min(bite, len(seq.req.prompt) - seq.prefill_pos)

    def assemble_ragged(self, width: int, extra_tokens: int = 0):
        """One packed ragged row batch for the single-dispatch engine step.

        Every active slot becomes one row of a (NS, width) token batch:
        decode-ready sequences contribute their pending token (plus
        ``extra_tokens`` draft columns the engine fills for speculative
        verify), sequences mid chunked-prefill contribute their next
        prompt chunk. Returns (tokens (NS, W), row_start (NS,), seq_lens
        (NS,), logit_idx (NS,), page_rows (NS, P), modes (NS,), decode,
        prefill):

          * ``row_start[s]`` — cache position of row s's first new token
          * ``seq_lens[s]`` — ``row_start + n_new`` (1 for inactive rows,
            whose pages are all -1 so the kernel's write lands on the
            pool's reserved trash page)
          * ``logit_idx[s]`` — first new-token row whose logits the host
            reads (0 for decode/verify, the last real row for a
            prompt-final chunk)
          * ``modes[s]`` — 0 inactive, 1 decode/verify, 2 prefill chunk
          * ``decode`` — the decode-ready ActiveSeqs (slot order)
          * ``prefill`` — ``[(seq, start, real, final)]``, one chunk per
            prefilling sequence (oldest first): ``real`` valid prompt
            tokens from position ``start``; ``final`` marks the chunk
            whose last row's logits sample the first generated token

        Shapes are static per (width, extra_tokens), so ONE jitted trace
        of the ragged step covers every decode / verify / prefill batch
        composition the engine can assemble.
        """
        ns, pps = self.max_slots, self.pages_per_slot
        tokens = np.zeros((ns, width), np.int32)
        row_start = np.zeros((ns,), np.int32)
        seq_lens = np.ones((ns,), np.int32)
        logit_idx = np.zeros((ns,), np.int32)
        modes = np.zeros((ns,), np.int32)
        page_rows = np.full((ns, pps), -1, np.int32)
        decode = self.decode_ready()
        for seq in decode:
            assert seq.req.generated, "active sequence with no pending token"
            tokens[seq.slot, 0] = seq.req.generated[-1]
            row_start[seq.slot] = seq.pos
            seq_lens[seq.slot] = seq.pos + 1 + extra_tokens
            modes[seq.slot] = 1
            page_rows[seq.slot, : len(seq.pages)] = seq.pages
        prefill = []
        for seq in self.prefilling():
            st = seq.prefill_pos
            real = self.planned_prefill_real(seq, width)
            if real <= 0:
                continue
            tokens[seq.slot, :real] = seq.req.prompt[st:st + real]
            row_start[seq.slot] = st
            seq_lens[seq.slot] = st + real
            final = st + real == len(seq.req.prompt)
            logit_idx[seq.slot] = real - 1 if final else 0
            modes[seq.slot] = 2
            page_rows[seq.slot, : len(seq.pages)] = seq.pages
            prefill.append((seq, st, real, final))
        # mirror assemble()'s peak-step sampling so bytes/token stats stay
        # comparable across step modes
        resident = int(sum(s.pos + (1 if s.prefill_pos is None else 0)
                           for s in self.active()))
        if self.pool.pages_in_use > self.peak_pages:
            self.peak_pages = self.pool.pages_in_use
            self.resident_at_peak = resident
        elif self.pool.pages_in_use == self.peak_pages:
            self.resident_at_peak = (resident if self.resident_at_peak == 0
                                     else min(self.resident_at_peak, resident))
        return (tokens, row_start, seq_lens, logit_idx, page_rows, modes,
                decode, prefill)
