"""Host-side page policy for the paged KV cache: the allocator and the
slot manager.

The device side is dumb on purpose: a global page pool
`(num_pages, page_size, KVH, hd)` per layer plus one `(B, max_pages)` int32
page table, read by the flash kernel's index maps (one table lookup per key
block) and written through by the decode scatter.  ALL policy lives here, on
the host, in plain Python:

  - a free list + per-page refcounts — freed slots return their pages, so
    pool occupancy tracks LIVE tokens instead of worst-case capacity;
  - prefix sharing: admitted token ids are hashed page-by-page into a chain
    (h_j = hash(h_{j-1}, tokens of page j)), and a new request whose prompt
    matches a registered chain reuses those physical pages with a refcount
    bump — a system prompt shared by N slots is stored ONCE;
  - copy-on-write: a write into a page with refcount > 1 first copies it to
    a fresh page (the caller does the device copy; `cow()` does the
    bookkeeping), so sharers never observe each other's tokens.

Page 0 is reserved as the TRASH page: dead page-table entries point at it,
so the masked decode writes of inactive slots and the culled key blocks of
short slots always index in-bounds without any device-side branching.

`SlotPages` puts the continuous scheduler's page decisions on top of the
allocator: each slot's run of pages, the admission watermark, group
placement with prefix matching and copy-on-write, growth of a run as a
slot decodes, victim choice for preemption, and the occupancy peaks.

Nothing here touches jax — the module is unit-testable with no device in
sight, and the serve scheduler mirrors every decision into the
device-side page table with tiny `.at[].set` writes.
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import numpy as np

#: reserved physical page every dead/unmapped table entry points at
TRASH_PAGE = 0

_CHAIN_SEED = 0x9E3779B9


def _chain(h: int, chunk: Tuple[int, ...], partial: bool) -> Tuple:
    """Key of the page holding `chunk` when the pages BEFORE it hash to `h`.
    Partial (tail) pages key on their exact token count too, so a 5-token
    tail never matches an 8-token page that happens to share a prefix."""
    return ("part" if partial else "full", h, chunk)


class PoolExhausted(RuntimeError):
    """The page pool has no free pages left for an allocation."""


class PageError(RuntimeError):
    """A page lifecycle violation: double free, freeing a shared page,
    retaining a dead page, or a conservation (leak) failure.  These are
    always caller bugs — the allocator refuses to limp along with corrupt
    refcounts, because a wrong refcount silently aliases two slots' KV."""


class PageAllocator:
    """Free list + refcounts + prefix registry over `num_pages` pages of
    `page_size` tokens (page 0 reserved as trash)."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("need at least one allocatable page + trash")
        if page_size < 1:
            raise ValueError(f"page_size must be positive, got {page_size}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._free = deque(range(1, num_pages))
        self._ref = {}          # page -> refcount (absent == free)
        self._registry = {}     # chain key -> page
        self._page_key = {}     # page -> chain key (for cleanup)
        self.cow_copies = 0     # total copy-on-write page copies

    # -- allocation ---------------------------------------------------------

    def alloc(self, n: int) -> List[int]:
        """Take `n` fresh pages (refcount 1)."""
        if n > len(self._free):
            raise PoolExhausted(
                f"need {n} pages, {len(self._free)} free of {self.num_pages}")
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def can_admit(self, tokens: int, reclaimable: int = 0) -> bool:
        """Watermark admission control: would a request writing `tokens`
        cache entries fit in the free pages plus `reclaimable` pages the
        scheduler could preempt back (non-shared pages of victim slots — the
        caller computes that sum, because only it knows which slots are
        preemptible)?  `tokens` counts every cache index the request will
        touch through its first decode write (prompt + 1).  The credit is
        capped at the pool's allocatable size: no amount of reclaim makes a
        request fit that a fully-free pool cannot hold."""
        need = -(-max(0, int(tokens)) // self.page_size)
        avail = len(self._free) + max(0, int(reclaimable))
        return need <= min(avail, self.num_pages - 1)

    def retain(self, pages: Iterable[int]) -> None:
        """Add one reference to each (already-live) page.  Retaining a freed
        page (or trash) is a hard error: it would resurrect recycled KV."""
        pages = list(pages)
        for p in pages:
            if p not in self._ref:
                raise PageError(f"retain of dead page {p} (refcount 0)")
        for p in pages:
            self._ref[p] += 1

    def release(self, pages: Iterable[int]) -> List[int]:
        """Drop one reference per page; pages reaching zero return to the
        free list (and leave the prefix registry).  Returns the freed ones.
        Releasing an already-free page is a hard error (double free)."""
        freed = []
        for p in pages:
            if p not in self._ref:
                raise PageError(f"double free of page {p} (refcount already 0)")
            self._ref[p] -= 1
            if self._ref[p] == 0:
                del self._ref[p]
                self.invalidate(p)
                self._free.append(p)
                freed.append(p)
        return freed

    def free(self, pages: Iterable[int]) -> None:
        """Hard-deallocate exclusively-owned pages.  Unlike `release` (a
        refcount decrement that tolerates sharing), `free` demands refcount
        exactly 1: freeing a shared page out from under its other referents,
        or a page that is already free, is a hard error."""
        pages = list(pages)
        for p in pages:
            r = self._ref.get(p, 0)
            if r == 0:
                raise PageError(f"double free of page {p} (refcount already 0)")
            if r > 1:
                raise PageError(
                    f"free of shared page {p} (refcount {r}); release() drops "
                    "one reference, free() requires exclusive ownership")
        for p in pages:
            del self._ref[p]
            self.invalidate(p)
            self._free.append(p)

    def leak_check(self) -> None:
        """Conservation invariant: every page is exactly one of free, live
        (refcount >= 1), or the reserved trash page.  Raises PageError on any
        leak, double-accounting, or trash-page corruption.  Called at
        end-of-serve in tests and every round under --check-invariants."""
        free = list(self._free)
        free_set = set(free)
        if len(free_set) != len(free):
            raise PageError("free list contains duplicates")
        if TRASH_PAGE in free_set or TRASH_PAGE in self._ref:
            raise PageError("trash page 0 entered the free list or went live")
        overlap = free_set & set(self._ref)
        if overlap:
            raise PageError(f"pages both free and live: {sorted(overlap)}")
        bad_ref = [p for p, r in self._ref.items() if r < 1]
        if bad_ref:
            raise PageError(f"live pages with refcount < 1: {sorted(bad_ref)}")
        total = len(free_set) + len(self._ref) + 1  # +1: trash
        if total != self.num_pages:
            raise PageError(
                f"page leak: {len(free_set)} free + {len(self._ref)} live "
                f"+ 1 trash = {total}, pool has {self.num_pages}")
        dangling = [p for p in self._page_key if p not in self._ref]
        if dangling:
            raise PageError(f"freed pages still registered: {sorted(dangling)}")

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def shared(self, page: int) -> bool:
        return self._ref.get(page, 0) > 1

    # -- prefix sharing -----------------------------------------------------

    def match_prefix(self, tokens: Sequence[int]) -> Tuple[List[int], int]:
        """Longest registered prefix of `tokens`, page by page.

        Returns (pages, covered_tokens).  Full pages chain first; a trailing
        partial page matches only if some slot registered exactly that tail
        (same tokens, same count) — the caller must treat a matched PARTIAL
        page as write-hazardous (it will CoW before appending into it).
        The caller still owns the refcount bump (``retain``)."""
        toks = tuple(int(t) for t in tokens)
        ps = self.page_size
        pages: List[int] = []
        covered = 0
        h = _CHAIN_SEED
        while covered + ps <= len(toks):
            chunk = toks[covered:covered + ps]
            page = self._registry.get(_chain(h, chunk, partial=False))
            if page is None:
                return pages, covered
            pages.append(page)
            covered += ps
            h = hash((h, chunk))
        rest = toks[covered:]
        if rest:
            page = self._registry.get(_chain(h, rest, partial=True))
            if page is not None:
                pages.append(page)
                covered += len(rest)
        return pages, covered

    def register_prefix(self, tokens: Sequence[int],
                        pages: Sequence[int]) -> None:
        """Publish `tokens` (living in `pages`, page_size per page, ragged
        tail allowed) so later admissions can share them.  Pages already
        registered under the same chain key (a matched shared prefix) are
        left alone; a first-writer-wins rule keeps the registry consistent
        when two identical prompts are admitted back to back."""
        toks = tuple(int(t) for t in tokens)
        ps = self.page_size
        h = _CHAIN_SEED
        for i, page in enumerate(pages):
            chunk = toks[i * ps:(i + 1) * ps]
            if not chunk:
                break
            key = _chain(h, chunk, partial=len(chunk) < ps)
            if key not in self._registry:
                self._registry[key] = page
                self._page_key[page] = key
            if len(chunk) < ps:
                break
            h = hash((h, chunk))

    def invalidate(self, page: int) -> None:
        """Unpublish `page` from the prefix registry (its content is about to
        change, or it was freed).  No-op for unregistered pages."""
        key = self._page_key.pop(page, None)
        if key is not None and self._registry.get(key) == page:
            del self._registry[key]

    def cow(self, page: int) -> int:
        """Copy-on-write bookkeeping for a write into a SHARED page: drop our
        reference on `page`, take a fresh page (refcount 1), count the copy.
        The caller performs the device-side content copy old -> new."""
        assert self.shared(page), f"page {page} not shared (ref {self.refcount(page)})"
        new = self.alloc(1)[0]
        self._ref[page] -= 1
        self.cow_copies += 1
        return new

    # -- occupancy stats ----------------------------------------------------

    def pages_live(self) -> int:
        """Distinct physical pages holding data (trash excluded)."""
        return len(self._ref)

    def pages_shared(self) -> int:
        """Physical pages referenced by more than one slot."""
        return sum(1 for r in self._ref.values() if r > 1)

    def pages_logical(self) -> int:
        """Page-table entries backed by live pages, counted PER SLOT — what a
        dense per-slot cache would have to store."""
        return sum(self._ref.values())

    def capacity_multiplier(self) -> float:
        """Logical / physical pages: >1 exactly when prefixes are shared —
        the effective-capacity win of paging + dedupe."""
        return self.pages_logical() / max(1, self.pages_live())

    def free_pages(self) -> int:
        return len(self._free)


@dataclasses.dataclass
class Placement:
    """What `SlotPages.place` decided for one admission group, in member
    order: `placed[i]` says whether member i got its pages (the others go
    back to the queue); `rows` holds each member's page-table row (trash
    where unplaced); `graft` the (rows, toks, pages, offs) coordinates of
    every unshared token to copy out of the mini cache, padded with
    (0, 0) -> trash writes to `slots * total` entries, so that each prompt
    length grafts at one shape; `cow` the (src, dst) page copies to make
    after the graft (a source may be a page the same group grafts); and
    `victims` the live slots evicted to make room."""

    placed: List[bool]
    rows: np.ndarray
    graft: Tuple[List[int], ...]
    cow: List[Tuple[int, int]]
    victims: List[int]


class SlotPages:
    """The page policy of a slot grid over one pool: the `PageAllocator`,
    each slot's run of pages, prefix sharing and copy-on-write, admission
    control, on-demand growth, victim choice and the occupancy peaks.

    Host-only: it decides and the scheduler writes.  Every method returns
    what the device has to mirror (table rows, graft coordinates, CoW
    pairs, growth pages, victims) and touches no device state itself.  A
    victim's pages are released here; the scheduler still repoints its
    table row at trash and requeues its request.

    `capacity` is a slot's KV capacity in tokens (its table row holds
    ceil(capacity / page_size) pages).  The pool defaults to the size at
    which growth can never exhaust it: every slot's full row plus one page
    of slack, and the trash page.  `share` turns prefix reuse on;
    `n_prefix` counts the positions in front of each prompt's tokens (the
    vlm patch prefix) that every admission writes."""

    def __init__(self, page_size: int, slots: int, capacity: int,
                 num_pages: Optional[int] = None, share: bool = True,
                 n_prefix: int = 0):
        self.page_size = int(page_size)
        self.max_pages = -(-int(capacity) // self.page_size)
        if num_pages is None:
            num_pages = 1 + slots * (self.max_pages + 1)
        self.alloc = PageAllocator(num_pages, page_size)
        self.num_pages = self.alloc.num_pages
        self.share = share
        self.n_prefix = n_prefix
        self.slots: List[List[int]] = [[] for _ in range(slots)]
        self._seq = [0] * slots     # placement order, for victim choice
        self._next = 0
        self.peaks = {"pages_live": 0, "pages_shared": 0,
                      "paged_capacity_multiplier": 0.0, "cow_copies": 0}

    # -- admission -----------------------------------------------------------

    def _match(self, tokens) -> Tuple[List[int], int]:
        return self.alloc.match_prefix(tokens) if self.share else ([], 0)

    def need(self, tokens: Sequence[int]) -> Optional[int]:
        """Fresh pages an admission of `tokens` takes through its first
        decode write (later writes grow on demand), net of a matched shared
        prefix and plus the CoW of a matched partial tail; None when the
        request could not fit even a fully-free pool."""
        total = len(tokens) + self.n_prefix
        need = total // self.page_size + 1
        if need > self.num_pages - 1:
            return None
        matched, covered = self._match(tokens)
        new = need - len(matched)
        if covered == total and total % self.page_size:
            new += 1
        return new

    def fits(self, n_pages: int, active: Sequence[bool]) -> bool:
        """The watermark: do `n_pages` fresh pages fit in the free pages
        plus what preempting live slots could reclaim?"""
        return self.alloc.can_admit(self.page_size * n_pages,
                                    reclaimable=self.reclaimable(active))

    def reclaimable(self, active: Sequence[bool]) -> int:
        """Pages preemption could free now: the unshared pages of the live
        slots (the ones `victim` may choose)."""
        return sum(1 for s, run in enumerate(self.slots) if active[s]
                   for p in run if not self.alloc.shared(p))

    def place(self, members, active: Sequence[bool]) -> Placement:
        """Give each member of one admission group (a list of (slot,
        tokens, decodes), all of one prompt length) its run of pages.

        The first pass matches each prompt against the registered prefixes,
        retains the matched pages, allocates the rest (evicting live slots
        if the pool moved since the watermark let the group in; a member
        that still does not fit is left unplaced) and registers the prompt.
        The second pass runs after every member has registered, so that
        identical prompts in one group share their partial tail before
        anyone writes it: a member that will decode copies a shared write
        page (the page of position `total`) or unpublishes an owned one."""
        ps = self.page_size
        total = len(members[0][1]) + self.n_prefix
        need = total // ps + 1
        placed = [False] * len(members)
        rows = np.zeros((len(members), self.max_pages), np.int64)
        graft = ([], [], [], [])
        cow, victims = [], []
        cow_reserve = 0     # pages the second pass's CoWs will take
        for i, (s, toks, decodes) in enumerate(members):
            while True:
                matched, covered = self._match(toks)
                # partial-page keys are exact-tail, so a matched partial
                # page covers the whole prompt: the graft never appends
                # into a shared page
                assert covered == total or covered % ps == 0, (covered, total)
                cow_tail = decodes and covered == total and total % ps != 0
                new = need - len(matched) + cow_tail
                if new + cow_reserve <= self.alloc.free_pages():
                    break
                if not self._free_up(new + cow_reserve, active, victims):
                    matched = None
                    break
                # the victims may have freed registered pages: match again
            if matched is None:
                continue
            self.alloc.retain(matched)
            run = matched + self.alloc.alloc(need - len(matched))
            cow_reserve += cow_tail
            if self.share:
                self.alloc.register_prefix(toks, run[:-(-len(toks) // ps)])
            self.slots[s] = run
            self._seq[s], self._next = self._next, self._next + 1
            placed[i] = True
            rows[i, :len(run)] = run
            for p in range(covered, total):
                for coord, v in zip(graft, (i, p, run[p // ps], p % ps)):
                    coord.append(v)
        widx = total // ps
        for i, (s, _, decodes) in enumerate(members):
            if not placed[i] or not decodes:
                continue
            p = self.slots[s][widx]
            if self.alloc.shared(p):
                new = self.alloc.cow(p)
                cow.append((p, new))
                self.slots[s][widx] = rows[i, widx] = new
            else:
                self.alloc.invalidate(p)
        pad = len(self.slots) * total - len(graft[0])
        graft = tuple(c + [0] * pad for c in graft)
        return Placement(placed, rows, graft, cow, victims)

    # -- growth and preemption -----------------------------------------------

    def victim(self, active: Sequence[bool], spared=()) -> Optional[int]:
        """The live slot to preempt: the newest placement (the least sunk
        prefill lost, and FIFO keeps older requests moving), skipping slots
        whose pages are all shared, since releasing them reclaims nothing,
        and the slots in `spared` (already evicted)."""
        best = None
        for s, run in enumerate(self.slots):
            if (active[s] and s not in spared
                    and any(not self.alloc.shared(p) for p in run)
                    and (best is None or self._seq[s] > self._seq[best])):
                best = s
        return best

    def _free_up(self, n_pages: int, active, victims: List[int]) -> bool:
        """Evict victims until `n_pages` pages are free; False when no
        victim is left."""
        while self.alloc.free_pages() < n_pages:
            v = self.victim(active, victims)
            if v is None:
                return False
            self.release(v)
            victims.append(v)
        return True

    def grow(self, s: int, last_pos: int, active: Sequence[bool],
             exhausted: Callable[[], bool] = lambda: False):
        """Grow slot s's run to cover every write up to position
        `last_pos`.  `exhausted()` is asked once for each page to allocate;
        True (an injected exhaustion) evicts a victim first, and so does a
        pool with no free page.  Returns ([(run index, page)] to write into
        s's table row, [victims evicted, in order]); it stops early when s
        itself was evicted, and then the caller skips s this round."""
        last = last_pos // self.page_size
        assert last < self.max_pages, (last, self.max_pages)
        writes, victims = [], []
        while len(self.slots[s]) <= last:
            forced = exhausted()
            while forced or not self.alloc.free_pages():
                v = self.victim(active, victims)
                if v is None and not forced:
                    # unreachable while s is live (a decoding slot always
                    # owns its unshared write page): the honest failure
                    # rather than a silent hang
                    raise PoolExhausted(
                        f"growth for slot {s}: no free page and no victim")
                forced = False
                if v is not None:
                    self.release(v)
                    victims.append(v)
                    if v == s:
                        return writes, victims
            p = self.alloc.alloc(1)[0]
            writes.append((len(self.slots[s]), p))
            self.slots[s].append(p)
        return writes, victims

    # -- release and accounting ----------------------------------------------

    def release(self, s: int) -> None:
        """Drop slot s's references; a no-op on a slot that holds none."""
        self.alloc.release(self.slots[s])
        self.slots[s] = []

    def sample(self) -> None:
        """Fold the pool's current occupancy into `peaks`."""
        a, pk = self.alloc, self.peaks
        pk["pages_live"] = max(pk["pages_live"], a.pages_live())
        pk["pages_shared"] = max(pk["pages_shared"], a.pages_shared())
        pk["paged_capacity_multiplier"] = max(
            pk["paged_capacity_multiplier"], a.capacity_multiplier())
        pk["cow_copies"] = a.cow_copies

    def leak_check(self) -> None:
        """Conservation: every page is free, live or the trash page,
        exactly once (`PageAllocator.leak_check`)."""
        self.alloc.leak_check()
