"""COW shared-prefix KV cache + lazy on-demand page growth (round 25,
``tpu_hc_bench/serve/prefix_cache.py`` + the refcounted allocator).

Default lane rides the ONE warmed session moe engine from conftest in
VirtualClock replays — zero new engine warmups.  The load-bearing pins:

- **refcount discipline**: pages are shared resources; a page rejoins
  the free list only at refcount zero, COW duplications are counted
  apart from pool recycling, and ``bind`` refuses dead pages;
- **trie correctness**: a node's path spells the full token prefix, a
  partial tail page is reusable only under its exact tail tuple, the
  trash page is never cached, and eviction is leaf-first and never
  touches a page a resident still holds;
- **parity**: sharing and lazy growth are allocation tricks — runs
  with the cache on decode token-for-token what the unshared engine
  decodes, with zero post-warmup compiles;
- **lint**: page-table stores and free-list motion outside
  ``PageAllocator`` are flagged at error severity in the serve package.
"""

from __future__ import annotations

import numpy as np
import pytest

from tpu_hc_bench import flags
from tpu_hc_bench.analysis import lints
from tpu_hc_bench.obs import metrics as obs_metrics
from tpu_hc_bench.serve import arrivals
from tpu_hc_bench.serve import cache as cache_mod
from tpu_hc_bench.serve import engine as engine_mod
from tpu_hc_bench.serve import prefix_cache as pc

from conftest import SERVE_VCOSTS

VCOSTS = dict(SERVE_VCOSTS, page_copy=0.001)


# --- the refcounted allocator -----------------------------------------


def test_allocator_share_free_refcount():
    a = cache_mod.PageAllocator(6)
    pages = a.alloc(2)
    assert pages and all(p != 0 for p in pages)
    assert all(a.refcount(p) == 1 for p in pages)
    a.share(pages)
    assert all(a.refcount(p) == 2 for p in pages)
    free_before = a.free_pages
    a.free(pages)                       # one holder drops: still live
    assert all(a.refcount(p) == 1 for p in pages)
    assert a.free_pages == free_before
    a.free(pages)                       # last holder: back in the pool
    assert all(a.refcount(p) == 0 for p in pages)
    assert a.free_pages == free_before + 2


def test_allocator_cow_counted_apart_from_recycled():
    a = cache_mod.PageAllocator(4)
    first = a.alloc(3)
    a.free(first)
    assert a.recycled == 0              # first hand-out is not a recycle
    again = a.alloc(2)
    assert a.recycled == 2              # genuine churn through alloc
    dst = a.cow_alloc()
    assert dst is not None and a.refcount(dst) == 1
    assert a.cow_copies == 1
    assert a.recycled == 2              # a COW is sharing, not churn
    a.free(again + [dst])


def test_allocator_bind_refuses_dead_page():
    a = cache_mod.PageAllocator(4)
    table = np.zeros(3, np.int32)
    (p,) = a.alloc(1)
    a.bind(table, 1, p)
    assert table[1] == p
    a.free([p])
    with pytest.raises(AssertionError):
        a.bind(table, 2, p)
    with pytest.raises(AssertionError):
        a.share([p])


# --- the prefix trie ---------------------------------------------------


def _cache(num_pages=16, ps=4):
    a = cache_mod.PageAllocator(num_pages)
    return a, pc.PrefixCache(a, page_size=ps)


def test_cache_match_walks_full_chunks():
    a, c = _cache()
    toks = list(range(100, 108))        # two full 4-token chunks
    pages = a.alloc(3)
    assert c.insert(toks, pages, len(toks)) == 2
    # the cache now holds its own ref on each retained page
    assert a.refcount(pages[0]) == 2 and a.refcount(pages[1]) == 2
    assert a.refcount(pages[2]) == 1    # slot past the prompt: private
    m = c.match(toks)
    assert m.pages == pages[:2] and m.tokens_covered == 8
    # a prefix diverging inside chunk 2 shares only chunk 1
    m = c.match(toks[:4] + [999, 998, 997, 996])
    assert m.pages == pages[:1] and m.tokens_covered == 4
    # acquire increfs per shared page for the admitted holder
    got = c.acquire(c.match(toks))
    assert got == pages[:2]
    assert a.refcount(pages[0]) == 3 and a.refcount(pages[1]) == 3


def test_cache_partial_tail_exact_key_only():
    a, c = _cache()
    toks = list(range(200, 206))        # one full chunk + 2-token tail
    pages = a.alloc(2)
    assert c.insert(toks, pages, len(toks)) == 2
    m = c.match(toks)
    assert m.pages == pages and m.partial_key == (204, 205)
    assert m.tokens_covered == 6
    # same chunk, different tail: the partial must NOT be offered
    m = c.match(toks[:4] + [777, 778])
    assert m.pages == pages[:1] and m.partial_key is None


def test_cache_never_retains_trash_page():
    a, c = _cache()
    (p1,) = a.alloc(1)
    # slot 1 routed to trash (a shared slot on the inserting request):
    # the walk stops there and nothing beyond it is cached
    assert c.insert(list(range(12)), [p1, 0, 0], 12) == 1
    assert a.refcount(p1) == 2
    m = c.match(list(range(12)))
    assert m.pages == [p1]


def test_cache_evicts_cold_leaves_never_held_pages():
    a, c = _cache(num_pages=8)
    hot = list(range(300, 308))
    cold = list(range(400, 408))
    hot_pages = a.alloc(2)
    cold_pages = a.alloc(2)
    c.insert(cold, cold_pages, 8)
    c.insert(hot, hot_pages, 8)
    resident = c.acquire(c.match(hot))  # a resident still reads these
    a.free(cold_pages)                  # the inserting requests retire
    a.free(hot_pages)
    # only the cold path is cache-only; the hot pages stay pinned by
    # the resident no matter how many the eviction asks for
    assert c.evict(4) == 2
    assert c.match(cold).pages == []
    assert c.match(hot).pages == hot_pages
    assert a.refcount(cold_pages[0]) == 0
    assert c.evicted_pages == 2
    # the resident retires: leaf first, then its exposed parent
    a.free(resident)
    assert c.evict(4) == 2
    assert c.match(hot).pages == []


# --- closed loops on the warmed session engine ------------------------


def _run(moe_engine, reqs, **policy):
    events = []
    writer = obs_metrics.MetricsWriter(None)
    writer.event = lambda kind, **f: events.append({"kind": kind, **f})
    summary = moe_engine.run(
        reqs, batching="continuous", writer=writer,
        clock=engine_mod.VirtualClock(VCOSTS), **policy)
    gen = {e["id"]: e.get("generated") for e in events
           if e["kind"] == "request"}
    return summary, events, gen


def _shared_prompt_trace(vocab, n, plen, seed=25):
    block = np.random.default_rng((seed, plen)).integers(
        0, vocab, size=plen, dtype=np.int32)
    return [arrivals.Request(rid=i, arrival_s=0.001 * i,
                             prompt=block.copy(), output_len=4)
            for i in range(n)]


def test_shared_prefix_run_matches_unshared_tokens(moe_engine):
    """The satellite-3 parity pin: identical 8-token prompts (two full
    chunks at page 4) decode the same streams with the cache on as off,
    while the ledger proves sharing actually happened."""
    reqs = _shared_prompt_trace(moe_engine.spec.vocab_size, 6, plen=8)
    off, _, gen_off = _run(moe_engine, reqs,
                           kv_reserve="lazy", prefix_cache="off")
    on, _, gen_on = _run(moe_engine, reqs,
                         kv_reserve="lazy", prefix_cache="on")
    assert gen_on == gen_off            # token-for-token
    assert all(v for v in gen_on.values())
    assert off["post_warmup_compiles"] == 0
    assert on["post_warmup_compiles"] == 0
    kvf = on["kv_pool"]
    assert kvf["prefix_lookups"] == 6
    assert kvf["prefix_hits"] >= 1      # everyone after the first
    assert kvf["prefix_pages_shared"] >= 2
    assert on["prefix_hit_frac"] == pytest.approx(
        kvf["prefix_hits"] / 6, abs=1e-4)
    assert on["kv_reserve"] == "lazy" and on["prefix_cache"] == "on"
    # the off arm never consulted a cache: structurally absent, not 0
    assert off["kv_pool"]["prefix_hit_frac"] is None


def test_shared_tail_triggers_cow_copy(moe_engine):
    """A 6-token prompt caches a partially-filled tail page; the
    owner's first decode append into it (refcount 2: owner + cache)
    must copy, not corrupt the cached prefix — and the copy is charged
    to ``cow_copies``, never ``recycled``."""
    reqs = _shared_prompt_trace(moe_engine.spec.vocab_size, 6, plen=6)
    off, _, gen_off = _run(moe_engine, reqs,
                           kv_reserve="lazy", prefix_cache="off")
    on, _, gen_on = _run(moe_engine, reqs,
                         kv_reserve="lazy", prefix_cache="on")
    assert gen_on == gen_off
    assert on["kv_pool"]["cow_copies"] >= 1
    assert on["post_warmup_compiles"] == 0


def test_lazy_reservation_raises_pool_util(moe_engine):
    """Same burst trace, same pool: lazy admission reserves only the
    prompt's pages (+headroom) so written/reserved page-seconds must
    strictly beat the worst-case control's."""
    cfg = flags.BenchmarkConfig(
        model="moe_tiny", workload="serve", arrival_rate=10000.0,
        num_requests=8, max_prompt_len=8, max_output_len=4,
        max_in_flight=2, kv_page_size=4, seed=0).resolve()
    reqs = arrivals.build_requests(cfg, moe_engine.spec.vocab_size)
    worst, _, gen_w = _run(moe_engine, reqs, kv_reserve="worst")
    lazy, _, gen_l = _run(moe_engine, reqs, kv_reserve="lazy")
    assert gen_l == gen_w               # reservation never changes tokens
    assert lazy["kv_pool_util"] > worst["kv_pool_util"]
    assert lazy["kv_req_gap_frac"] < worst["kv_req_gap_frac"]
    assert worst["kv_reserve"] == "worst" and lazy["kv_reserve"] == "lazy"


def test_on_demand_growth_grows_and_accounts(moe_engine):
    """With headroom 0 every page past the prompt's is allocated the
    step its first token lands: the run must grow, stamp per-request
    ``pages_grown``, and still match the worst-case arm's tokens."""
    reqs = _shared_prompt_trace(moe_engine.spec.vocab_size, 4, plen=4)
    worst, _, gen_w = _run(moe_engine, reqs, kv_reserve="worst")
    saved = moe_engine.cfg.kv_growth_headroom
    moe_engine.cfg.kv_growth_headroom = 0
    try:
        lazy, ev, gen_l = _run(moe_engine, reqs, kv_reserve="lazy")
    finally:
        moe_engine.cfg.kv_growth_headroom = saved
    assert gen_l == gen_w
    # plen 4 + output 4 writes 7 tokens = 2 pages; 1 reserved, 1 grown
    assert lazy["kv_pool"]["pages_grown"] == 4
    grown = [e["pages_grown"] for e in ev if e["kind"] == "request"]
    assert grown == [1, 1, 1, 1]
    assert lazy["pages_grown_total"] == 4
    assert lazy["post_warmup_compiles"] == 0


def test_policy_flags_validated_at_run():
    cfg = flags.BenchmarkConfig(model="moe_tiny", workload="serve")
    with pytest.raises(ValueError, match="kv_reserve"):
        flags.BenchmarkConfig(model="moe_tiny", workload="serve",
                              kv_reserve="sometimes").resolve()
    with pytest.raises(ValueError, match="prefix_cache"):
        flags.BenchmarkConfig(model="moe_tiny", workload="serve",
                              prefix_cache="maybe").resolve()
    # sharing requires lazy reservation: with worst-case tables there
    # is nothing for a cache hit to save
    with pytest.raises(ValueError, match="lazy"):
        flags.BenchmarkConfig(model="moe_tiny", workload="serve",
                              prefix_cache="on").resolve()
    assert cfg  # plain defaults resolve elsewhere in the suite


# --- the page-refcount-discipline lint --------------------------------


BAD_TABLE_STORE = """
def admit(fl, page):
    fl.table[0] = page
"""

BAD_FREELIST = """
def retire(self, pages):
    self._free.extend(pages)
    self.free_list.append(pages[0])
"""

ALLOCATOR_INTERNAL = """
class PageAllocator:
    def free(self, pages):
        for p in pages:
            self._free.append(p)
    def bind(self, table, slot, page):
        table[slot] = page
"""

PLURAL_OK = """
def collect(tables, i, fl):
    tables[i] = fl.table
"""


def _lint(src):
    return [f for f in lints.lint_source_text(
        src, filename="tpu_hc_bench/serve/engine.py")
        if f.lint == lints.PAGE_REFCOUNT]


def test_refcount_lint_flags_table_store_and_freelist():
    found = _lint(BAD_TABLE_STORE)
    assert len(found) == 1 and "bind" in found[0].message
    found = _lint(BAD_FREELIST)
    assert len(found) == 2
    assert all("PageAllocator" in f.message for f in found)


def test_refcount_lint_exempts_allocator_and_plurals():
    assert _lint(ALLOCATOR_INTERNAL) == []
    assert _lint(PLURAL_OK) == []
    # outside the serve package: not this lint's business
    assert not [f for f in lints.lint_source_text(
        BAD_TABLE_STORE, filename="tpu_hc_bench/train/driver.py")
        if f.lint == lints.PAGE_REFCOUNT]


def test_refcount_lint_registered_and_suppressable():
    assert lints.PAGE_REFCOUNT in lints.ALL_SOURCE_LINTS
    src = BAD_TABLE_STORE.replace(
        "fl.table[0] = page",
        "fl.table[0] = page  # tpu-hc: disable=page-refcount-discipline")
    assert _lint(src) == []


def test_repo_serve_sources_refcount_clean():
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    serve_dir = os.path.join(repo, "tpu_hc_bench", "serve")
    found = []
    for name in sorted(os.listdir(serve_dir)):
        if name.endswith(".py"):
            found.extend(lints.lint_file(os.path.join(serve_dir, name)))
    found = [f for f in found if f.lint == lints.PAGE_REFCOUNT]
    assert found == [], [f.message for f in found]
