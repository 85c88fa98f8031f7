"""Radix prefix cache tests (docs/SERVING.md § Radix prefix cache).

Covers the properties the subsystem is built around:
  * refcounted allocator soundness — free XOR rc>=1 partition, exact
    slot+tree accounting, release-exactly-once under sharing (incl. a
    randomized alloc/share/free property test);
  * tree mechanics — per-page trie insert/match, partial tails, LRU leaf
    eviction under a budget, pool-pressure reclaim, pinning;
  * engine integration — greedy generation WITH prefix reuse is
    token-for-token identical to the cache-off oracle across mid-flight
    admits, evictions, copy-on-write divergence, and a supervisor
    restart (tree dropped cleanly, pin intents survive), with ZERO
    ``new_shape`` ledger events;
  * chaos — injected ``page_oom`` through the prefix admission path
    leaves every request terminal and the invariants intact;
  * frontend — ``ClassPolicy.shared_prefix`` pre-warms + pins.
"""

import numpy as np
import pytest

from deeplearning4j_tpu import faults, observe
from deeplearning4j_tpu.models.gpt import (
    GptConfig, GptModel, reference_generate,
)
from deeplearning4j_tpu.serving import (
    GenerativeEngine, PagedKVCache, RadixPrefixCache,
)

CFG = GptConfig.tiny()
MODEL = GptModel(CFG, seed=1)

SYS = np.arange(1, 12, dtype=np.int32)  # 11 tokens: 1 full page + 3 tail
                                        # at page_size=8


def make_engine(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_pages_per_seq", 6)
    kw.setdefault("max_prompt", 16)
    kw.setdefault("seed", 3)
    kw.setdefault("prefix_pages", 12)
    kw.setdefault("suffix_bucket", 8)
    return GenerativeEngine(MODEL, **kw)


def assert_oracle(prompt, res, n=None):
    n = len(res.tokens) if n is None else n
    np.testing.assert_array_equal(
        res.tokens, reference_generate(MODEL.params, CFG, prompt, n))


# ---------------------------------------------------------------------------
# refcounted allocator (satellite: check_invariants in the refcount era)
# ---------------------------------------------------------------------------


class TestRefcountAllocator:
    def make_cache(self, **kw):
        kw.setdefault("layers", 2)
        kw.setdefault("row_width", 16)
        kw.setdefault("page_size", 4)
        kw.setdefault("num_pages", 8)
        kw.setdefault("max_slots", 3)
        kw.setdefault("max_pages_per_seq", 4)
        return PagedKVCache(**kw)

    def test_share_release_exactly_once(self):
        """Two slots share a page run; each free_slot releases once; the
        pages enter the free list exactly once (the satellite-6 double-
        free regression, pinned on the free-list counters)."""
        c = self.make_cache()
        assert c.ensure_capacity(0, 8) == "ok"  # 2 private pages
        run = list(c.owned[0])
        for p in run:
            c.map_shared(1, p)  # slot 1 shares slot 0's run
        c.check_invariants()
        assert c.refcount[run[0]] == 2
        free_before = c.free_pages
        c.free_slot(0)
        assert c.free_pages == free_before  # slot 1 still holds them
        c.check_invariants()
        c.free_slot(0)  # idempotent: nothing left to release
        assert c.free_pages == free_before
        c.free_slot(1)
        assert c.free_pages == c.num_pages
        for p in run:
            assert c.free.count(p) == 1, "page entered the free list twice"
        c.check_invariants()

    def test_retain_release_guards(self):
        c = self.make_cache()
        page = c.alloc_page()
        c.release(page)
        with pytest.raises(AssertionError, match="double free"):
            c.release(page)
        with pytest.raises(AssertionError, match="free list"):
            c.retain(page)

    def test_tree_refs_exact_accounting(self):
        c = self.make_cache()
        assert c.ensure_capacity(0, 4) == "ok"
        page = c.owned[0][0]
        c.retain(page)  # a "tree" reference
        c.check_invariants(tree_refs={page: 1})
        with pytest.raises(AssertionError, match="tree refs"):
            c.check_invariants(tree_refs={})  # rc 2 but only 1 slot holder
        c.free_slot(0)
        c.check_invariants(tree_refs={page: 1})
        c.release(page)
        c.check_invariants(tree_refs={})
        assert c.free_pages == c.num_pages

    def test_cow_page_copies_device_state(self):
        import jax.numpy as jnp

        c = self.make_cache()
        src = c.alloc_page()
        c.owned[0].append(src)
        c.page_table[0, 0] = src
        c.kv = c.kv.at[:, :, src].set(7.0)
        dst = c.cow_page(1, src)
        assert dst is not None and dst != src
        np.testing.assert_array_equal(np.asarray(c.kv[:, :, dst]),
                                      np.asarray(c.kv[:, :, src]))
        assert c.page_table[1, 0] == dst and c.owned[1] == [dst]
        c.check_invariants()
        c.kv = c.kv.at[:, :, dst].set(9.0)  # writes never alias the source
        assert float(jnp.max(jnp.abs(c.kv[:, :, src] - 7.0))) == 0.0

    def test_cow_page_pool_exhausted(self):
        c = self.make_cache(num_pages=1)
        src = c.alloc_page()
        assert c.cow_page(0, src) is None
        c.release(src)
        c.check_invariants()

    def test_randomized_alloc_share_free_property(self):
        """Satellite 1: random interleavings of grow/share/free/tree-
        retain/tree-release never break the partition or the exact
        refcount accounting."""
        r = np.random.RandomState(0)
        c = self.make_cache(num_pages=12, max_slots=4, max_pages_per_seq=5)
        tree: dict = {}  # page -> refs (the model "tree")
        for step in range(400):
            op = r.randint(5)
            slot = int(r.randint(c.max_slots))
            if op == 0:  # grow
                c.ensure_capacity(slot, int(r.randint(1, 21)))
            elif op == 1:  # free
                c.free_slot(slot)
            elif op == 2:  # share a live page into a slot with row room
                live = [p for o in c.owned for p in o] + list(tree)
                if live and len(c.owned[slot]) < c.max_pages_per_seq:
                    c.map_shared(slot, live[int(r.randint(len(live)))])
            elif op == 3:  # tree retains a live page
                live = [p for o in c.owned for p in o] + list(tree)
                if live:
                    p = live[int(r.randint(len(live)))]
                    c.retain(p)
                    tree[p] = tree.get(p, 0) + 1
            else:  # tree releases
                if tree:
                    p = list(tree)[int(r.randint(len(tree)))]
                    c.release(p)
                    tree[p] -= 1
                    if not tree[p]:
                        del tree[p]
            c.check_invariants(tree_refs=tree)
        for slot in range(c.max_slots):
            c.free_slot(slot)
        for p in list(tree):
            for _ in range(tree.pop(p)):
                c.release(p)
        c.check_invariants(tree_refs={})
        assert c.free_pages == c.num_pages


# ---------------------------------------------------------------------------
# radix tree mechanics (no engine)
# ---------------------------------------------------------------------------


class TestRadixTree:
    def setup_tree(self, max_pages=8, num_pages=24):
        cache = PagedKVCache(layers=1, row_width=8, page_size=4,
                             num_pages=num_pages, max_slots=2,
                             max_pages_per_seq=6)
        return cache, RadixPrefixCache(cache, max_pages=max_pages)

    def grab(self, cache, n):
        return [cache.alloc_page() for _ in range(n)]

    def release_run(self, cache, pages):
        for p in pages:
            cache.release(p)

    def test_insert_match_full_and_tail(self):
        cache, tree = self.setup_tree()
        toks = np.arange(10, dtype=np.int32)  # 2 full pages + 2-token tail
        pages = self.grab(cache, 3)
        assert tree.insert(toks, pages) == 3
        self.release_run(cache, pages)  # the "slot" lets go; tree holds
        cache.check_invariants(tree_refs=tree.page_refs())
        m = tree.match(np.arange(12, dtype=np.int32))
        assert m is not None and m.matched == 10
        assert m.pages == pages
        # identical prompt: capped at len-1 so one token re-prefills
        m = tree.match(toks)
        assert m.matched == 9 and m.pages == pages
        # mid-page divergence against a FULL page: CoW-able tail match
        div = np.asarray([0, 1, 2, 3, 4, 5, 99, 98], np.int32)
        m = tree.match(div)
        assert m.matched == 6 and m.pages == pages[:2]
        tree.check_invariants()

    def test_min_match_gate(self):
        cache, tree = self.setup_tree()
        pages = self.grab(cache, 1)
        tree.insert(np.arange(4, dtype=np.int32), pages)
        self.release_run(cache, pages)
        assert tree.match(np.asarray([0, 1, 9, 9, 9], np.int32)) is None
        assert tree.match(np.arange(6, dtype=np.int32)).matched == 4

    def test_dedup_insert_refreshes_not_duplicates(self):
        cache, tree = self.setup_tree()
        toks = np.arange(8, dtype=np.int32)
        pages = self.grab(cache, 2)
        assert tree.insert(toks, pages) == 2
        self.release_run(cache, pages)
        dup = self.grab(cache, 2)
        assert tree.insert(toks, dup) == 0  # deduplicated
        self.release_run(cache, dup)  # slot's copies free entirely
        assert tree.tree_pages == 2
        cache.check_invariants(tree_refs=tree.page_refs())

    def test_lru_leaf_eviction_under_budget(self):
        cache, tree = self.setup_tree(max_pages=3)
        a = self.grab(cache, 2)
        tree.insert(np.arange(8, dtype=np.int32), a)       # path A: 2 nodes
        self.release_run(cache, a)
        b = self.grab(cache, 2)
        tree.insert(np.arange(50, 58, dtype=np.int32), b)  # path B: 2 nodes
        self.release_run(cache, b)
        # budget 3: the LRU leaf (path A's deepest node) evicted first
        assert tree.tree_pages == 3
        m = tree.match(np.arange(10, dtype=np.int32))
        assert m is not None and m.matched == 4  # A's first page survives
        assert tree.match(np.arange(50, 60, dtype=np.int32)).matched == 8
        cache.check_invariants(tree_refs=tree.page_refs())

    def test_evict_to_free_and_reclaimable(self):
        cache, tree = self.setup_tree(max_pages=8, num_pages=4)
        pages = self.grab(cache, 4)
        tree.insert(np.arange(16, dtype=np.int32), pages)
        self.release_run(cache, pages)
        assert cache.free_pages == 0
        assert tree.reclaimable_pages() == 4
        freed = tree.evict_to_free(2)
        assert freed == 2 and cache.free_pages == 2
        assert tree.tree_pages == 2
        cache.check_invariants(tree_refs=tree.page_refs())

    def test_slot_shared_pages_are_not_reclaimable(self):
        """A tree page an active slot still maps frees NOTHING when
        evicted — it must not count as reclaimable supply (the admission
        precheck would turn a backpressure wait into a spurious terminal
        oom). evict_to_free still evicts such a leaf as a FALLBACK to
        unblock freeable ancestors behind it, and reports only what
        actually reached the free list."""
        cache, tree = self.setup_tree(max_pages=8, num_pages=4)
        pages = self.grab(cache, 4)
        tree.insert(np.arange(16, dtype=np.int32), pages)
        self.release_run(cache, pages)
        cache.map_shared(0, pages[-1])  # a "mid-flight hit" holds the two
        cache.map_shared(0, pages[-2])  # deepest nodes of the chain
        assert tree.reclaimable_pages() == 2  # only the slot-free pair
        # the slot-held leaves get evicted as fallbacks (freeing nothing
        # now, releasing the tree refs) to reach the freeable ancestors
        assert tree.evict_to_free(4) == 2
        assert cache.free_pages == 2
        cache.check_invariants(tree_refs=tree.page_refs())
        free_before = cache.free_pages
        cache.free_slot(0)  # slot retires: the fallback-evicted pages free
        assert cache.free_pages == free_before + 2

    def test_unusable_match_does_not_refresh_lru(self):
        """A path whose uncached tail exceeds max_suffix can never serve
        a hit — matching it must not refresh its LRU stamps, or
        never-usable entries crowd serving ones out of the budget."""
        cache, tree = self.setup_tree(max_pages=8)
        a = self.grab(cache, 1)
        tree.insert(np.arange(4, dtype=np.int32), a)
        self.release_run(cache, a)
        b = self.grab(cache, 1)
        tree.insert(np.arange(50, 54, dtype=np.int32), b)
        self.release_run(cache, b)
        stamp = {n.tokens: n.last_used for n in tree._all_nodes()}
        long_tail = np.concatenate([np.arange(4), np.arange(90, 110)]) \
            .astype(np.int32)
        assert tree.match(long_tail, max_suffix=2) is None
        assert {n.tokens: n.last_used
                for n in tree._all_nodes()} == stamp  # untouched
        assert tree.match(np.arange(6, dtype=np.int32),
                          max_suffix=2).matched == 4  # usable: refreshes

    def test_pinned_never_evicted_and_intents_survive_clear(self):
        cache, tree = self.setup_tree(max_pages=2, num_pages=24)
        toks = np.arange(8, dtype=np.int32)
        pages = self.grab(cache, 2)
        tree.insert(toks, pages)
        self.release_run(cache, pages)
        assert tree.pin(toks) == 2
        assert tree.reclaimable_pages() == 0
        assert tree.evict_to_free(1) == 0  # nothing evictable
        # budget pressure cannot displace the pinned path either
        other = self.grab(cache, 2)
        tree.insert(np.arange(50, 58, dtype=np.int32), other)
        self.release_run(cache, other)
        assert tree.match(np.arange(9, dtype=np.int32)).matched == 8
        # clear drops pages but keeps the pin INTENT: re-insert re-pins
        tree.clear()
        assert tree.tree_pages == 0 and tree.pinned_pages == 0
        cache.check_invariants(tree_refs={})
        again = self.grab(cache, 2)
        tree.insert(toks, again)
        self.release_run(cache, again)
        assert tree.pinned_pages == 2
        tree.check_invariants()

    def test_pin_intent_covers_rebuilt_divergence_tails(self):
        """Regression: after clear(), traffic re-inserts the pinned
        system prompt's mid-page remainder only EMBEDDED in its own
        divergence tails (rem + traffic tokens, never rem exactly). The
        intent must pin one covering tail — page-aligned-only coverage
        would silently leave the mid-page KV evictable."""
        cache, tree = self.setup_tree(max_pages=8, num_pages=24)
        sysp = np.arange(6, dtype=np.int32)  # 1 full page + 2-token rem
        pages = self.grab(cache, 2)
        tree.insert(sysp, pages)
        self.release_run(cache, pages)
        tree.pin(sysp)
        assert tree.pinned_pages == 2
        tree.clear()
        # traffic rebuild: sysp + a request-specific token — the partial
        # tail key is (4, 5, 9), not the intent's (4, 5)
        rebuilt = self.grab(cache, 2)
        tree.insert(np.concatenate([sysp, np.asarray([9], np.int32)]),
                    rebuilt)
        self.release_run(cache, rebuilt)
        assert tree.pinned_pages == 2  # full page AND a covering tail
        assert tree.reclaimable_pages() == 0
        assert tree.evict_to_free(2) == 0  # the mid-page KV is protected
        assert tree.match(np.concatenate(
            [sysp, np.asarray([9, 9], np.int32)])).matched >= 6
        # a second traffic tail must NOT grow the pin set without bound
        more = self.grab(cache, 2)
        tree.insert(np.concatenate([sysp, np.asarray([7], np.int32)]),
                    more)
        self.release_run(cache, more)
        assert tree.pinned_pages == 2
        tree.check_invariants()

    def test_zero_budget_rejected(self):
        cache, _ = self.setup_tree()
        with pytest.raises(ValueError, match="max_pages"):
            RadixPrefixCache(cache, max_pages=0)


# ---------------------------------------------------------------------------
# engine integration: oracle equality with reuse (satellite: test coverage)
# ---------------------------------------------------------------------------


class TestPrefixEngine:
    def test_hits_are_oracle_identical_with_zero_new_shape(self):
        observe.reset()
        eng = make_engine()
        p1 = np.concatenate([SYS, np.asarray([50, 51], np.int32)])
        p2 = np.concatenate([SYS, np.asarray([60], np.int32)])
        hits = []
        for p in (p1, p2, p1, p2):
            res = eng.generate([p], max_new_tokens=5, eos_token=-1)[0]
            assert res.finish_reason == "length"
            assert_oracle(p, res)
            hits.append(res.prefix_hit_tokens)
        assert hits[0] == 0              # cold: full prefill, inserted
        assert all(h >= 8 for h in hits[1:])  # warm: shared-prefix hits
        assert hits[2] == p1.size - 1    # exact repeat: all but one token
        eng.check_invariants()
        serving = [e for e in observe.ledger().events()
                   if e.graph == "serving"]
        assert not any(e.cause == "new_shape" for e in serving)
        keys = {e.key for e in serving}
        assert "suffix_prefill" in keys and "copy_page" in keys
        m = observe.metrics()
        assert m.counter("dl4j_tpu_prefix_hits_total").value == 3
        assert m.counter("dl4j_tpu_prefix_hit_tokens_total").value \
            == sum(hits)

    def test_hit_tokens_ride_the_result(self):
        eng = make_engine()
        p = np.concatenate([SYS, np.asarray([50], np.int32)])
        eng.generate([p], max_new_tokens=2, eos_token=-1)
        res = eng.generate([np.concatenate(
            [SYS, np.asarray([77], np.int32)])],
            max_new_tokens=2, eos_token=-1)[0]
        assert res.prefix_hit_tokens == SYS.size  # full pages + CoW tail

    def test_cow_divergence_does_not_corrupt_donor(self):
        """Two prompts diverge MID-PAGE: the second CoWs the tail page;
        both must match the oracle, and replaying the first afterwards
        must still match (its cached page was never written)."""
        observe.reset()
        eng = make_engine()
        a = np.concatenate([SYS, np.asarray([50, 51], np.int32)])
        b = np.concatenate([SYS[:9], np.asarray([70, 71, 72], np.int32)])
        assert_oracle(a, eng.generate([a], max_new_tokens=4,
                                      eos_token=-1)[0])
        res_b = eng.generate([b], max_new_tokens=4, eos_token=-1)[0]
        assert res_b.prefix_hit_tokens == 9  # 8 full + 1 shared tail token
        assert_oracle(b, res_b)
        res_a2 = eng.generate([a], max_new_tokens=4, eos_token=-1)[0]
        assert res_a2.prefix_hit_tokens >= 11
        assert_oracle(a, res_a2)
        assert observe.metrics().counter(
            "dl4j_tpu_prefix_cow_copies_total").value >= 2
        eng.check_invariants()

    @pytest.mark.parametrize("tail,hit", [
        ([50], True),                  # suffix prefill into the CoW'd tail
        ([50, 51, 52, 53, 54], True),  # ... and on into a fresh page
        (list(range(50, 59)), False),  # over the bucket: whole-page write
    ])
    def test_shared_pages_keep_their_bits(self, tail, hit):
        """An admission beside a cached prefix (by the suffix path or by
        the whole-page ``write_prompt``) and the decode steps after it
        never write a page the tree holds: bit for bit."""
        eng = make_engine(max_prompt=24)
        warm = np.concatenate([SYS, np.asarray([40], np.int32)])
        eng.generate([warm], max_new_tokens=2, eos_token=-1)
        held = sorted(eng.prefix.page_refs())
        assert held
        before = np.asarray(eng.cache.kv[:, :, held])
        p = np.concatenate([SYS, np.asarray(tail, np.int32)])
        res = eng.generate([p], max_new_tokens=7, eos_token=-1)[0]
        assert (res.prefix_hit_tokens > 0) is hit
        assert_oracle(p, res)
        np.testing.assert_array_equal(
            np.asarray(eng.cache.kv[:, :, held]), before)
        eng.check_invariants()

    def test_midflight_admits_with_shared_prefix(self):
        """Several same-prefix requests through 2 slots with different
        budgets: mid-flight turnover, shared pages across LIVE slots,
        every output oracle-exact, every page accounted for."""
        eng = make_engine()
        warm = np.concatenate([SYS, np.asarray([40], np.int32)])
        eng.generate([warm], max_new_tokens=2, eos_token=-1)
        prompts = [np.concatenate([SYS, np.asarray([50 + i], np.int32)])
                   for i in range(5)]
        budgets = [3, 8, 2, 6, 4]
        futs = [eng.submit(p, max_new_tokens=b, eos_token=-1)
                for p, b in zip(prompts, budgets)]
        while eng.scheduler.has_work():
            eng.step()
        for p, b, f in zip(prompts, budgets, futs):
            res = f.result(timeout=0)
            assert res.finish_reason == "length"
            assert res.prefix_hit_tokens >= 8
            np.testing.assert_array_equal(
                res.tokens, reference_generate(MODEL.params, CFG, p, b))
        eng.check_invariants()
        # every non-tree page came home
        assert eng.cache.free_pages == \
            eng.cache.num_pages - eng.prefix.tree_pages

    def test_suffix_over_bucket_falls_back_to_full_prefill(self):
        eng = make_engine(suffix_bucket=2)
        warm = np.concatenate([SYS, np.asarray([40], np.int32)])
        eng.generate([warm], max_new_tokens=2, eos_token=-1)
        p = np.concatenate([SYS, np.asarray([50, 51, 52], np.int32)])
        res = eng.generate([p], max_new_tokens=3, eos_token=-1)[0]
        assert res.prefix_hit_tokens == 0  # suffix 3 > bucket 2
        assert_oracle(p, res)

    def test_eviction_pressure_keeps_serving_correctly(self):
        """A tiny tree budget under many distinct prompts: evictions
        churn, correctness and invariants hold, pages never leak."""
        observe.reset()
        eng = make_engine(prefix_pages=4)
        r = np.random.RandomState(5)
        for _ in range(8):
            p = r.randint(1, CFG.vocab_size, size=int(r.randint(9, 15))) \
                .astype(np.int32)
            assert_oracle(p, eng.generate([p], max_new_tokens=3,
                                          eos_token=-1)[0])
            eng.check_invariants()
        assert observe.metrics().counter(
            "dl4j_tpu_prefix_evicted_pages_total").value > 0
        assert eng.prefix.tree_pages <= 4

    def test_supervisor_restart_drops_tree_cleanly(self):
        """A mid-generation crash: the tree is dropped (its device KV
        died with reset_kv), the retried request still matches the
        oracle, zero new_shape across the recovery, and the tree rebuilds
        from the retire-insert."""
        observe.reset()
        eng = make_engine(restart_backoff_s=0.0)
        p = np.concatenate([SYS, np.asarray([50, 51], np.int32)])
        eng.generate([p], max_new_tokens=3, eos_token=-1)
        assert eng.prefix.tree_pages > 0
        faults.arm("decode_step_error", prob=1.0, after_n=1, max_fires=1)
        try:
            res = eng.generate([p], max_new_tokens=5, eos_token=-1)[0]
        finally:
            faults.reset()
        assert eng.restarts == 1
        assert_oracle(p, res, 5)
        eng.check_invariants()
        assert eng.prefix.tree_pages > 0  # rebuilt at retire
        serving = [e for e in observe.ledger().events()
                   if e.graph == "serving"]
        assert not any(e.cause == "new_shape" for e in serving)

    def test_page_oom_mid_match_is_terminal_and_sound(self):
        """Satellite 2 (unit leg): injected pool pressure firing through
        the PREFIX admission path — after shared pages are mapped —
        unwinds the slot, retires the request terminally as oom, and
        leaves exact refcount accounting intact."""
        eng = make_engine(max_slots=1)
        p = np.concatenate([SYS, np.asarray([50], np.int32)])
        eng.generate([p], max_new_tokens=2, eos_token=-1)
        faults.arm("page_oom", prob=1.0, max_fires=1)
        try:
            res = eng.generate([p], max_new_tokens=2, eos_token=-1)[0]
        finally:
            faults.reset()
        assert res.finish_reason == "oom"
        eng.check_invariants()
        res = eng.generate([p], max_new_tokens=2, eos_token=-1)[0]
        assert res.finish_reason == "length"  # pressure gone: serves again
        assert_oracle(p, res)

    def test_pool_pressure_waits_instead_of_spurious_oom(self):
        """Regression: when the only 'reclaimable' tree pages are the
        matched prefix's OWN pages (about to be consumed, not freed),
        the admission precheck must take the backpressure WAIT path —
        not admit, fail to reclaim, and retire the request terminally as
        oom one step before a blocker would have freed real pages."""
        eng = make_engine(max_slots=2, num_pages=4, prefix_pages=3)
        warm = np.concatenate([SYS, np.asarray([40], np.int32)])
        eng.generate([warm], max_new_tokens=1, eos_token=-1)
        assert eng.prefix.tree_pages == 2  # sysp full page + partial tail
        blocker = eng.submit(np.arange(100, 108, dtype=np.int32),
                             max_new_tokens=5, eos_token=-1)
        eng.step()  # blocker admits: free list now empty
        assert eng.cache.free_pages == 0
        victim = eng.submit(np.concatenate(
            [SYS, np.asarray([50], np.int32)]),
            max_new_tokens=3, eos_token=-1)
        while eng.scheduler.has_work():
            eng.step()
        assert blocker.result(timeout=0).finish_reason == "length"
        res = victim.result(timeout=0)
        assert res.finish_reason == "length", res.finish_reason  # not oom
        assert res.prefix_hit_tokens >= 8  # and the match survived
        assert_oracle(np.concatenate([SYS, np.asarray([50], np.int32)]),
                      res)
        eng.check_invariants()

    def test_disabled_by_default(self):
        eng = GenerativeEngine(MODEL, max_slots=2, page_size=8,
                               max_pages_per_seq=6, max_prompt=16)
        assert eng.prefix is None
        p = np.asarray([3, 5, 7, 9], np.int32)
        res = eng.generate([p], max_new_tokens=3)[0]
        assert res.prefix_hit_tokens == 0
        assert eng.cache.free_pages == eng.cache.num_pages


# ---------------------------------------------------------------------------
# frontend pre-warm + pinning (ClassPolicy.shared_prefix)
# ---------------------------------------------------------------------------


class TestFrontendSharedPrefix:
    def test_prewarm_pins_and_first_request_hits(self):
        from deeplearning4j_tpu.serving import ClassPolicy, SLOFrontend

        observe.reset()
        eng = make_engine()
        classes = {
            "interactive": ClassPolicy("interactive", priority=0,
                                       degradable=False,
                                       shared_prefix=SYS.tolist()),
            "batch": ClassPolicy("batch", priority=2),
        }
        fe = SLOFrontend(eng, classes=classes)
        assert eng.prefix.pinned_pages > 0
        eng.start()
        try:
            fut = fe.submit(np.concatenate(
                [SYS, np.asarray([90], np.int32)]),
                slo_class="interactive", max_new_tokens=3, eos_token=-1)
            res = fut.result(timeout=120)
        finally:
            eng.stop()
        assert res.finish_reason == "length"
        assert res.prefix_hit_tokens >= 8  # hit from the FIRST request
        assert_oracle(np.concatenate([SYS, np.asarray([90], np.int32)]),
                      res)

    def test_prewarm_skipped_when_prefix_disabled(self):
        from deeplearning4j_tpu.serving import ClassPolicy, SLOFrontend

        eng = GenerativeEngine(MODEL, max_slots=2, page_size=8,
                               max_pages_per_seq=6, max_prompt=16)
        classes = {"standard": ClassPolicy("standard", priority=1,
                                           shared_prefix=[1, 2, 3])}
        fe = SLOFrontend(eng, classes=classes)  # must not raise
        assert eng.prefix is None
        assert fe.classes["standard"].shared_prefix == [1, 2, 3]
