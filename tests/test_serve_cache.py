"""``serve/cache.py``: the cache's one door, tested with no engine and
no JAX — the reservation rule, growth, copy-on-write, pause, eviction
before a pause, and conservation over every way a resident leaves."""

from __future__ import annotations

import subprocess
import sys

import numpy as np
import pytest

from tpu_hc_bench.serve import cache as cache_mod

PAGE = 4
WIDTH = 4           # table slots: 16 tokens of context


def _cache(num_pages=9, **kw):
    return cache_mod.CacheManager(num_pages, PAGE, WIDTH, **kw)


def _feed(n, start=0):
    return np.arange(start, start + n, dtype=np.int32)


def _resident(cache, feed):
    """Admit ``feed`` the way the loop does: the grant into a Holding."""
    assert cache.blocked_on(feed) is None
    g = cache.admit(feed)
    return cache_mod.Holding(pages=g.pages, table=g.table,
                             length=len(feed), slot=g.slot,
                             prefix_shared=g.shared), g


def _append(cache, fl, copies=None):
    """One decode step's cache calls for one row."""
    ok = cache.make_writable(
        fl, lambda s, d: copies.append((s, d)) if copies is not None
        else None)
    if ok:
        cache.token(fl.length)
        fl.length += 1
    return ok


def _resident_full(cache, fl):
    """``fl`` moved to its next page edge (no new page needed until
    there)."""
    while fl.length % PAGE:
        assert _append(cache, fl)
    return fl


def _assert_empty(cache, num_pages=9, slots=0):
    if cache.prefix is not None:
        cache.prefix.evict(num_pages)
    assert cache.free_pages == num_pages - 1
    assert cache.ledger.reserved_now == 0
    assert cache.ledger.written_now == 0
    if slots:
        assert cache.slots.free_slots == slots - 1


def test_module_imports_neither_jax_nor_the_engine():
    code = ("import sys, tpu_hc_bench.serve.cache; "
            "assert 'jax' not in sys.modules; "
            "assert 'tpu_hc_bench.serve.engine' not in sys.modules")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_worst_case_against_lazy_reservation_for_the_same_request():
    feed = _feed(6)                         # 2 pages of prompt
    worst = _cache()
    fl, g = _resident(worst, feed)
    assert len(g.pages) == WIDTH and worst.free_pages == 8 - WIDTH
    assert worst.ledger.reserved_now == WIDTH
    assert worst.ledger.written_now == 2
    lazy = _cache(kv_reserve="lazy", growth_headroom=1)
    fl, g = _resident(lazy, feed)
    assert len(g.pages) == 3                # prompt + headroom
    assert lazy.ledger.reserved_now == 3 and lazy.ledger.written_now == 2
    assert list(g.table[:3]) == g.pages and g.table[3] == 0
    assert g.write_table is g.table         # nothing shared: one table
    # the reservation never exceeds the table
    full = _cache(kv_reserve="lazy", growth_headroom=3)
    assert len(full.admit(_feed(13)).pages) == WIDTH


def test_admission_names_the_resource_that_binds():
    c = _cache(num_pages=6, state_slots=2)  # 5 pages, ONE usable slot
    feed = _feed(4)
    fl, g = _resident(c, feed)
    assert g.slot == 1 and g.table[WIDTH] == 1 and len(g.table) == WIDTH + 1
    # slot and pages both short: the slot is named first
    assert c.blocked_on(feed) == "slot_starved"
    c.release(fl)
    assert c.blocked_on(feed) is None
    squeezed = _cache(num_pages=6, squeezed=lambda: 2)
    assert squeezed.free_now() == 3
    assert squeezed.blocked_on(feed) == "pool_starved"
    assert squeezed.worst_case_room() == 0


def test_growth_at_a_page_edge():
    c = _cache(kv_reserve="lazy", growth_headroom=0)
    fl, g = _resident(c, _feed(7))          # 2 pages, 1 slot left in page 2
    assert len(fl.pages) == 2
    assert _append(c, fl) and len(fl.pages) == 2    # token 8: same page
    assert c.pages_grown == 0
    assert _append(c, fl)                           # token 9: a new page
    assert len(fl.pages) == 3 and fl.pages_grown == 1
    assert c.pages_grown == 1 and fl.table[2] == fl.pages[2]
    assert c.ledger.reserved_now == 3 and c.ledger.written_now == 3
    assert c.snapshot()["pages_grown"] == 1


def test_copy_on_write_named_for_a_shared_tail_only():
    c = _cache(kv_reserve="lazy", growth_headroom=1, prefix_cache=True)
    feed = _feed(6)                         # one full chunk + a tail of 2
    owner, _ = _resident(c, feed)
    copies: list = []
    # exclusive tail (the trie holds nothing yet): no copy
    assert c.make_writable(owner, lambda s, d: copies.append((s, d)))
    assert copies == []
    c.seed(feed, owner.pages, len(feed))    # trie now shares both pages
    tail = owner.pages[1]
    assert _append(c, owner, copies)
    assert copies == [(tail, owner.pages[1])] and owner.pages[1] != tail
    assert owner.table[1] == owner.pages[1]
    assert c.snapshot()["pages_cow"] == 1
    # a second request with the same prompt shares chunk AND tail, and
    # its prefill's WRITE table sends the shared slots to page 0
    twin, g = _resident(c, feed)
    assert g.shared == 2 and g.pages[:2] == [owner.pages[0], tail]
    assert list(g.write_table[:2]) == [0, 0]
    assert list(g.table[:2]) == g.pages[:2]
    snap = c.snapshot()
    assert (snap["prefix_lookups"], snap["prefix_hits"],
            snap["prefix_pages_shared"]) == (2, 1, 2)
    for fl in (owner, twin):
        c.release(fl)
    _assert_empty(c)


def test_pause_when_the_squeezed_pool_has_no_page():
    held = [0]
    c = _cache(kv_reserve="lazy", growth_headroom=0,
               squeezed=lambda: held[0])
    fl, _ = _resident(c, _feed(8))          # exactly two full pages
    held[0] = c.free_pages                  # every free page withheld
    assert not _append(c, fl)               # paused: nothing changed
    assert fl.length == 8 and len(fl.pages) == 2 and c.pages_grown == 0
    held[0] = 0
    assert _append(c, fl) and len(fl.pages) == 3


def test_eviction_before_a_pause():
    c = _cache(num_pages=4, kv_reserve="lazy", growth_headroom=0,
               prefix_cache=True)
    gone, _ = _resident(c, _feed(4, start=100))
    c.seed(_feed(4, start=100), gone.pages, 4)
    c.release(gone)                         # only the trie holds its page
    fl, _ = _resident(c, _feed(8))
    assert c.free_pages == 0
    assert _append(c, fl)                   # the cold page is evicted
    assert len(fl.pages) == 3 and c.prefix.evicted_pages == 1
    assert not _append(c, _resident_full(c, fl))    # nothing left: pause


def test_reclaim_evicts_towards_an_admission():
    c = _cache(num_pages=4, kv_reserve="lazy", growth_headroom=0,
               prefix_cache=True)
    old, _ = _resident(c, _feed(8, start=50))
    c.seed(_feed(8, start=50), old.pages, 8)
    c.release(old)
    assert c.free_pages == 1
    feed = _feed(8)
    assert c.blocked_on(feed) == "pool_starved"
    assert c.reclaim(feed) == 1 and c.blocked_on(feed) is None
    assert _cache().reclaim(feed) == 0      # no trie: nothing to reclaim


def _exit_ok(c, fl, feed):
    for _ in range(5):
        assert _append(c, fl)
    return c.release(fl)


def _exit_shed_resident(c, fl, feed):
    assert _append(c, fl)
    return c.release(fl)


def _exit_quarantined_at_prefill(c, fl, feed):
    return c.release(fl)                    # before the trie is seeded


def _exit_quarantined_at_decode(c, fl, feed):
    assert _append(c, fl)
    assert c.make_writable(fl, lambda s, d: None)   # the step ran,
    return c.release(fl)                            # no token landed


def _exit_preempted(c, fl, feed):
    assert _append(c, fl) and _append(c, fl)
    c.release(fl)
    again, _ = _resident(c, np.concatenate([feed, _feed(1, start=900)]))
    c.seed(feed, again.pages, len(feed))
    assert _append(c, again)
    return c.release(again)


def _exit_drained(c, fl, feed):
    other, _ = _resident(c, _feed(5, start=300))
    assert _append(c, other) and _append(c, fl)
    c.release(fl)
    return c.release(other)


@pytest.mark.parametrize("kv_reserve, prefix", [
    ("worst", False), ("lazy", False), ("lazy", True)],
    ids=["worst", "lazy", "lazy+prefix"])
@pytest.mark.parametrize("leave", [
    _exit_ok, _exit_shed_resident, _exit_quarantined_at_prefill,
    _exit_quarantined_at_decode, _exit_preempted, _exit_drained],
    ids=lambda f: f.__name__[6:])
def test_every_exit_conserves_pages_slots_and_ledger(leave, kv_reserve,
                                                     prefix):
    slots = 3
    c = _cache(num_pages=13, state_slots=slots, kv_reserve=kv_reserve,
               growth_headroom=1, prefix_cache=prefix)
    feed = _feed(6)
    fl, _ = _resident(c, feed)
    if leave is not _exit_quarantined_at_prefill:
        c.seed(feed, fl.pages, len(feed))
    final = leave(c, fl, feed)
    assert final >= 2 and fl.slot == 0
    _assert_empty(c, num_pages=13, slots=slots)
    fold = c.fold_args()
    assert fold["pages_peak"] >= 2 and fold["reserved_page_s"] == 0.0
    c.charge(0.5)                           # nothing held: nothing charged
    assert c.ledger.reserved_page_s == 0.0
