"""The serving lane's cache, behind one door.

A run's scheduler (``serve.loop``) talks to ONE object, ``CacheManager``:
it owns the page pool's allocator, the recurrent-state slots of a family
that keeps them, the utilization ledger, the optional shared-prefix trie
(``serve.prefix_cache``) and the reservation rule, and it answers the
questions the scheduler has — can this request be admitted now and, if
not, which resource binds; admit it; make this step's append slot
writable; a resident leaves.  The device's arrays and the compiled
programs stay with the engine: nothing here sees a ``jax.Array``, and a
copy-on-write is NAMED (source page, destination page) for the caller to
perform.  Host-only (NumPy at most), importable without JAX or an engine.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import numpy as np

from tpu_hc_bench.serve import prefix_cache as prefix_mod


class PageAllocator:
    """Refcounted free-list allocator over the KV page pool; page 0 is
    the reserved trash page (padded/inactive rows read and write it)
    and is never handed out.

    Round 25 makes pages a SHARED resource: a physical page can be
    held by several requests (a prefix-cache hit) and by the cache
    itself, so every holder takes a reference (``alloc``/``share``)
    and drops it through ``free`` — a page returns to the free list
    only when its last holder lets go.  All page-table stores and
    free-list motion live inside this class (``bind`` is the one
    sanctioned table store); the ``page-refcount-discipline`` lint
    pins that invariant at the source level, because a bare
    ``free_list.append`` beside a nonzero refcount is exactly the
    silent-corruption class COW introduces.

    Counter semantics (the r22 ``obs timeline`` counter track reads
    these, so they must stay honest):

    - ``recycled`` counts a page handed out again by ``alloc`` after a
      genuine free — the pool-churn signal a leak (pages freed but
      never reused) hides.
    - ``cow_copies`` counts copy-on-write page duplications
      (``cow_alloc``).  A COW is NOT a recycle: the page it pops was
      already churned through ``alloc``'s account when it last left
      the free list, and folding copies into ``recycled`` would read
      as pool churn when it is sharing traffic.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(
                f"KV pool needs >= 2 pages (one is the reserved trash "
                f"page): {num_pages}")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, 0, -1))
        self.pages_peak = 0
        self.recycled = 0
        self.cow_copies = 0
        self._ever_used = [False] * num_pages
        self._refcount = [0] * num_pages

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - 1 - len(self._free)

    def _take(self, count_recycle: bool) -> int:
        p = self._free.pop()
        self._refcount[p] = 1
        if self._ever_used[p]:
            if count_recycle:
                self.recycled += 1
        else:
            self._ever_used[p] = True
        return p

    def alloc(self, n: int) -> list[int] | None:
        if n > len(self._free):
            return None
        out = [self._take(count_recycle=True) for _ in range(n)]
        if self.used_pages > self.pages_peak:
            self.pages_peak = self.used_pages
        return out

    def cow_alloc(self) -> int | None:
        """One page for a copy-on-write duplication: counted under
        ``cow_copies``, never ``recycled`` (see class docstring)."""
        if not self._free:
            return None
        p = self._take(count_recycle=False)
        self.cow_copies += 1
        if self.used_pages > self.pages_peak:
            self.pages_peak = self.used_pages
        return p

    def share(self, pages: list[int]) -> None:
        """One additional reference per page (a prefix-cache hit or
        the cache's own retention hold)."""
        for p in pages:
            assert self._refcount[p] > 0, f"share of unheld page {p}"
            self._refcount[p] += 1

    def refcount(self, page: int) -> int:
        return self._refcount[page]

    def free(self, pages: list[int]) -> None:
        """Drop one reference per page; a page rejoins the free list
        at refcount zero (sole-holder frees behave exactly like the
        pre-r25 allocator)."""
        for p in pages:
            assert self._refcount[p] > 0, f"free of unheld page {p}"
            self._refcount[p] -= 1
            if self._refcount[p] == 0:
                self._free.append(p)

    def bind(self, table: np.ndarray, slot: int, page: int) -> None:
        """The one sanctioned page-table store: point ``table[slot]``
        at a page this allocator has handed out and still tracks."""
        assert self._refcount[page] > 0, f"bind of unheld page {page}"
        table[slot] = page


class SlotAllocator:
    """Free list over the recurrent-state slots of a family whose cache
    tree has a ``state`` pool (``serve.decode``): a request owns one
    slot from admit to finish; slot 0 is the reserved trash slot
    (inactive rows name it) and is never handed out.  A slot is never
    shared and never scrubbed: the prefill program starts every
    residency from a zero state whatever the slot held."""

    def __init__(self, num_slots: int):
        if num_slots < 2:
            raise ValueError(f"state pool needs >= 2 slots (one is the "
                             f"trash slot): {num_slots}")
        self.num_slots = num_slots
        self._idle = list(range(num_slots - 1, 0, -1))

    @property
    def free_slots(self) -> int:
        return len(self._idle)

    def alloc(self) -> int | None:
        return self._idle.pop() if self._idle else None

    def free(self, slot: int) -> None:
        assert 0 < slot < self.num_slots and slot not in self._idle, (
            f"free of unheld slot {slot}")
        self._idle.append(slot)


class KVLedger:
    """Round 22 (obs.kv): the KV-pool utilization ledger — pages
    reserved by admission vs pages actually written, integrated over
    step wall into the page-seconds behind ``kv_pool_util``.

    Writer-side bookkeeping, by declared limit: "written" is inferred
    from scheduler state (prompt length at admit, one token per decode
    step), not device introspection — the compiled programs do write
    those slots, but nothing here reads HBM back.  Every update is a
    couple of host int/float ops, pinned under the round-17
    1%-of-step-wall guard by test.
    """

    __slots__ = ("page_size", "reserved_now", "written_now",
                 "reserved_page_s", "written_page_s")

    def __init__(self, page_size: int):
        self.page_size = page_size
        self.reserved_now = 0       # pages held by in-flight requests
        self.written_now = 0        # pages with >= 1 written token
        self.reserved_page_s = 0.0
        self.written_page_s = 0.0

    def admit(self, pages_reserved: int, prompt_len: int) -> None:
        self.reserved_now += pages_reserved
        self.written_now += -(-prompt_len // self.page_size)

    def grow(self, n: int = 1) -> None:
        """Round 25 on-demand growth: pages taken mid-flight extend the
        holder's reservation from the moment they are bound (written
        follows through ``token`` when the boundary token lands)."""
        self.reserved_now += n

    def token(self, length_before: int) -> None:
        # one appended token touches a new page iff the pre-append
        # length sits on a page boundary — O(1) per generated token
        if length_before % self.page_size == 0:
            self.written_now += 1

    def retire(self, pages_reserved: int, length: int) -> int:
        """Release a request's pages; returns its final written-page
        count (== peak under worst-case reservation: lengths only grow
        and pages free only at retirement)."""
        final = -(-length // self.page_size)
        self.reserved_now -= pages_reserved
        self.written_now -= final
        return final

    def charge(self, dt: float) -> None:
        self.reserved_page_s += self.reserved_now * dt
        self.written_page_s += self.written_now * dt


@dataclasses.dataclass
class Holding:
    """What one resident holds of the cache tree.  ``CacheManager`` is
    the only writer of ``pages``, ``table``, ``pages_grown`` and
    ``slot``; the scheduler advances ``length`` as tokens land (after
    telling the ledger through ``CacheManager.token``)."""

    pages: list[int]
    table: np.ndarray               # int32 [table_cols]
    length: int = 0                 # tokens in KV cache
    # round 25 (lazy reservation + prefix sharing): pages grown on
    # demand after admission, and page slots admitted pointing at
    # shared prefix-cache pages — the footprint record stamps both
    pages_grown: int = 0
    prefix_shared: int = 0
    # the recurrent-state slot (0 = none: the family keeps no state)
    slot: int = 0
    # the window layers' ring: fixed pages from admit to finish (empty:
    # the family has no window layers)
    ring: list[int] = dataclasses.field(default_factory=list)


class Grant(NamedTuple):
    """What admission bound for one request: its pages (shared prefix
    pages first), the decode table, the prefill's WRITE table, the
    state slot (0: none), how many leading pages are shared and the
    window layers' ring (empty: none)."""

    pages: list[int]
    table: np.ndarray
    write_table: np.ndarray
    slot: int
    shared: int
    ring: list[int] | tuple = ()


class CacheManager:
    """One run's cache: pages, state slots, ledger, prefix trie and the
    reservation rule, behind the calls the scheduler makes.

    ``kv_reserve`` is ``"worst"`` (admission binds the whole table) or
    ``"lazy"`` (the prompt's pages plus ``growth_headroom``; every later
    page is an on-demand growth).  ``squeezed`` returns the pages an
    injected fault withholds right now (``None``: never any);
    ``free_now`` is the ONE view of head-room every decision below takes.
    """

    def __init__(self, num_pages: int, page_size: int, table_width: int,
                 *, state_slots: int = 0, ring_pages: int = 0,
                 ring_width: int = 0, kv_reserve: str = "worst",
                 growth_headroom: int = 0, prefix_cache: bool = False,
                 squeezed: Callable[[], int] | None = None):
        self.page_size = page_size
        self.table_width = table_width
        self.lazy = kv_reserve == "lazy"
        self.growth_headroom = growth_headroom
        self.allocator = PageAllocator(num_pages)
        self.slots = SlotAllocator(state_slots) if state_slots else None
        # the window layers' ring pool: ``ring_width`` pages a resident,
        # fixed from admit to finish (a window's pages are reused in
        # place as it slides, so it never grows)
        self.ring_width = ring_width
        self.rings = PageAllocator(ring_pages) if ring_width else None
        # the table handed to the programs: the pages, the ring, the slot
        self.table_cols = (table_width + ring_width
                           + (1 if state_slots else 0))
        self.ledger = KVLedger(page_size)
        # the trie lives per run: it holds references into THIS run's
        # allocator
        self.prefix = (prefix_mod.PrefixCache(self.allocator, page_size)
                       if prefix_cache else None)
        # residents can need a page mid-flight (growth or copy-on-write)
        self.on_demand = self.lazy or self.prefix is not None
        self._squeezed = squeezed
        self.pages_grown = 0
        self.prefix_hits = 0
        self.prefix_lookups = 0
        self.prefix_pages_shared = 0
        # a token was appended / device time passed: the ledger's own
        # methods, so a step pays one call for each as it always has
        self.token = self.ledger.token
        self.charge = self.ledger.charge

    # -- head-room -----------------------------------------------------

    @property
    def free_pages(self) -> int:
        return self.allocator.free_pages

    @property
    def pages_peak(self) -> int:
        return self.allocator.pages_peak

    def free_now(self) -> int:
        """Allocator free pages minus any injected pool squeeze."""
        f = self.allocator.free_pages
        if self._squeezed is not None:
            f -= self._squeezed()
        return max(0, f)

    def worst_case_room(self) -> int:
        """How many whole-table requests the pool could take now (the
        static arm's batch bound)."""
        return self.free_now() // self.table_width

    def _table_slots(self, plen: int) -> int:
        """Table slots admission binds for a ``plen``-token prefill: the
        reservation rule, in one place."""
        if not self.lazy:
            return self.table_width
        return min(self.table_width,
                   -(-plen // self.page_size) + self.growth_headroom)

    def _need_pages(self, feed) -> int:
        """Pages admission must pull from the FREE list for this feed
        right now: the bound slots minus the prefix-cache cover (the
        peek is pure — ``admit`` acquires in the same iteration)."""
        slots = self._table_slots(len(feed))
        if self.prefix is not None:
            slots -= self.prefix.match(feed).slots
        return max(0, slots)

    # -- admission -----------------------------------------------------

    def blocked_on(self, feed) -> str | None:
        """``None`` when a request with this prefill feed can be admitted
        now, else the resource that binds: ``"slot_starved"`` (every
        state slot is held, paused rows keep theirs: only a retirement
        frees one) before ``"pool_starved"``."""
        if self.slots is not None and not self.slots.free_slots:
            return "slot_starved"
        if self.free_now() < self._need_pages(feed) or (
                self.rings is not None
                and self.rings.free_pages < self.ring_width):
            return "pool_starved"
        return None

    def reclaim(self, feed) -> int:
        """Starved admission: evict cold prefix-cache pages (free
        capacity the trie is merely keeping warm) towards this feed's
        shortfall; pages freed."""
        if self.prefix is None:
            return 0
        return self.prefix.evict(self._need_pages(feed) - self.free_now())

    def admit(self, feed) -> Grant:
        """Bind a request whose ``blocked_on(feed)`` read ``None``:
        shared prefix pages, fresh pages, the slot, both tables."""
        plen = len(feed)
        shared: list[int] = []
        if self.prefix is not None:
            self.prefix_lookups += 1
            m = self.prefix.match(feed)
            if m.slots:
                self.prefix_hits += 1
                shared = self.prefix.acquire(m)
                self.prefix_pages_shared += len(shared)
        fresh = self.allocator.alloc(
            max(0, self._table_slots(plen) - len(shared)))
        assert fresh is not None, "admission checked free pages"
        pages = shared + fresh
        slot = 0
        table = np.pad(np.asarray(pages, np.int32),
                       (0, self.table_width - len(pages)))
        ring: list[int] | tuple = ()
        if self.rings is not None:
            ring = self.rings.alloc(self.ring_width)
            assert ring is not None, "admission checked the ring pool"
            # the ring rides in the columns after the pages
            table = np.append(table, np.asarray(ring, np.int32))
        if self.slots is not None:
            slot = self.slots.alloc()
            assert slot is not None, "admission checked the slots"
            # the slot rides in one more column, after the pages
            table = np.append(table, np.int32(slot))
        self.ledger.admit(len(pages), plen)
        write_table = table
        if shared:
            # the prefill-skip seam: shared slots' physical pages
            # already hold this prefix's K/V bitwise (same params,
            # same absolute positions, deterministic prefill), so
            # the WRITE table routes their stores to trash page 0
            # — the decode table keeps the real shared ids.  The
            # dense pass itself still runs: next_token attends
            # over every prompt position either way.
            write_table = np.where(
                np.arange(self.table_cols) < len(shared),
                0, table).astype(np.int32)
        return Grant(pages, table, write_table, slot, len(shared), ring)

    def seed(self, feed, pages: list[int], plen: int) -> None:
        """After a finite, non-quarantined prefill: seed the trie with
        its pages — full chunks as nodes, the partial tail under its
        exact-token key; the trie's own reference keeps them alive past
        the request's retirement."""
        if self.prefix is not None:
            self.prefix.insert(feed, pages, plen)

    # -- residency -----------------------------------------------------

    def make_writable(self, fl: Holding,
                      copy_page: Callable[[int, int], None]) -> bool:
        """Round 25 growth/COW pre-pass for one resident: make this
        step's append slot a writable, exclusively-owned page.
        Crossing a page boundary allocates from the free list AT THAT
        MOMENT (on-demand growth); the first append into a shared page
        duplicates it: ``copy_page(src, dst)`` is the caller running
        its page-copy program.  A cold prefix-cache page is evicted
        before either gives up.  Returns False to PAUSE the row this
        step — its batch slot masks off and nothing is written, so the
        next step retries after eviction, preemption, or a retirement
        frees pages."""
        slot = fl.length // self.page_size
        allocator = self.allocator
        if slot < len(fl.pages):
            page = fl.pages[slot]
            if allocator.refcount(page) == 1:
                return True
        else:
            page = None
        if self.free_now() < 1 and self.prefix is not None:
            self.prefix.evict(1)
        if self.free_now() < 1:
            return False
        if page is None:
            grown = allocator.alloc(1)
            allocator.bind(fl.table, slot, grown[0])
            fl.pages.append(grown[0])
            self.ledger.grow(1)
            fl.pages_grown += 1
            self.pages_grown += 1
            return True
        # shared tail page (this holder + the trie and/or other
        # residents): copy before the write
        dst = allocator.cow_alloc()
        copy_page(page, dst)
        allocator.bind(fl.table, slot, dst)
        fl.pages[slot] = dst
        allocator.free([page])
        return True

    def release(self, fl: Holding) -> int:
        """A resident leaves (finished, shed, quarantined, preempted or
        drained alike): retired from the ledger, pages and slot given
        back.  Returns its final written-page count."""
        final = self.ledger.retire(len(fl.pages), fl.length)
        self.allocator.free(fl.pages)
        if fl.ring:
            self.rings.free(fl.ring)
            fl.ring = []
        if fl.slot:
            self.slots.free(fl.slot)
            fl.slot = 0
        return final

    # -- telemetry -----------------------------------------------------

    def snapshot(self) -> dict:
        """The ``kv_pool`` record's fields: counters held here, no
        device round-trips."""
        ledger, allocator = self.ledger, self.allocator
        return {
            "pages_reserved": ledger.reserved_now,
            "pages_written": ledger.written_now,
            "free_pages": allocator.free_pages,
            "pages_peak": allocator.pages_peak,
            "pages_recycled": allocator.recycled,
            "reserved_page_s": round(ledger.reserved_page_s, 6),
            "written_page_s": round(ledger.written_page_s, 6),
            "pages_grown": self.pages_grown,
            "pages_cow": allocator.cow_copies,
            "prefix_hits": self.prefix_hits,
            "prefix_lookups": self.prefix_lookups,
            "prefix_pages_shared": self.prefix_pages_shared,
        }

    def fold_args(self) -> dict:
        """``obs.kv.fold_ledger``'s arguments, the request records
        apart."""
        return {
            "reserved_page_s": self.ledger.reserved_page_s,
            "written_page_s": self.ledger.written_page_s,
            "pages_peak": self.allocator.pages_peak,
            "pages_recycled": self.allocator.recycled,
            "pages_grown": self.pages_grown,
            "cow_copies": self.allocator.cow_copies,
            "prefix_hits": self.prefix_hits,
            "prefix_lookups": self.prefix_lookups,
            "prefix_pages_shared": self.prefix_pages_shared,
        }
