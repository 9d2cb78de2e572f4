"""Block pool + hybrid prefix cache: unit + hypothesis property tests."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.blockpool import PREFIX, TRANSFER, BlockPool
from repro.core.prefix_cache import HybridPrefixCache, token_block_hashes


def make_cache(blocks=256, bt=4, full=True, linear=True):
    pool = BlockPool(blocks, block_tokens=bt, block_bytes=1024)
    return HybridPrefixCache(pool, 0, 512, has_full_attn=full,
                             has_linear=linear)


class TestBlockPool:
    def test_alloc_free_cycle(self):
        p = BlockPool(8, 4)
        a = p.allocate(4)
        assert len(a) == 4 and p.free_blocks == 4
        p.release(a)
        assert p.free_blocks == 8       # unpopulated -> truly freed
        p.check_invariants()

    def test_transfer_blocks_discarded_on_release(self):
        """Paper Fig.4: transfer-cache blocks die when the wire finishes."""
        p = BlockPool(8, 4)
        t = p.allocate(3, TRANSFER)
        p.mark_populated(t)
        p.release(t)
        assert p.free_blocks == 8
        assert all(b not in p._blocks for b in t)

    def test_prefix_blocks_cached_then_evictable(self):
        p = BlockPool(4, 4)
        a = p.allocate(4, PREFIX)
        p.mark_populated(a)
        p.release(a)                     # rc=0 but cached (LRU)
        assert p.free_blocks == 4        # evictable counts as free
        b = p.allocate(4)                # forces eviction of all 4
        assert len(b) == 4
        assert p.stats["evicted"] == 4
        p.check_invariants()

    def test_overallocate_fails_cleanly(self):
        p = BlockPool(4, 4)
        a = p.allocate(3)
        assert p.allocate(2) is None
        assert p.stats["alloc_fail"] == 1
        p.release(a)

    def test_exhausted_all_refheld_alloc_fails(self):
        """With every block ref-counted (live requests), allocation must
        fail cleanly — nothing is evictable — and succeed again once refs
        drop to the LRU."""
        p = BlockPool(6, 4)
        a = p.allocate(6)
        p.mark_populated(a)
        p.retain(a)                      # rc=2: pinned by a second user
        assert p.allocate(1) is None
        assert p.stats["alloc_fail"] == 1
        assert p.stats["evicted"] == 0   # eviction never touches ref-held
        p.release(a)
        assert p.allocate(1) is None     # rc=1: still pinned
        p.release(a)                     # rc=0: populated -> LRU
        assert p.allocate(1) is not None
        assert p.stats["evicted"] == 1
        p.check_invariants()

    def test_lru_never_reclaims_refheld_or_unpopulated(self):
        """Eviction may only take rc=0 populated prefix blocks: ref-held
        blocks never enter the LRU, and unpopulated blocks free outright
        instead of lingering as (garbage) cache."""
        p = BlockPool(4, 4)
        held = p.allocate(2)             # rc=1 for the whole test
        cached = p.allocate(2)
        p.mark_populated(cached)
        p.release(cached)                # rc=0 + populated -> LRU
        got = p.allocate(2)              # free list empty: must evict
        assert set(got) == set(cached)   # ...exactly the LRU pair
        assert p.stats["evicted"] == 2
        for bid in held:
            assert p.get(bid).ref_count == 1
        p.release(got)                   # unpopulated at rc=0
        assert all(bid not in p._lru for bid in got)
        assert p.stats["freed"] == 2     # freed, not cached
        p.release(held)
        p.check_invariants()

    def test_transfer_discard_on_complete_even_if_retained(self):
        """Transfer blocks die the moment their last reference drops —
        populated or not, retained mid-flight or not — and never reach the
        LRU (paper Fig. 4: the transfer cache is not reusable)."""
        p = BlockPool(8, 4)
        t = p.allocate(3, TRANSFER)
        p.retain(t)                      # e.g. sender + receiver views
        p.mark_populated(t)
        p.release(t)                     # transfer completes on one side
        assert all(b in p._blocks for b in t)
        p.release(t)                     # last ref: discard, not cache
        assert all(b not in p._blocks for b in t)
        assert len(p._lru) == 0
        assert p.free_blocks == 8
        assert p.stats["freed"] == 3 and p.stats["evicted"] == 0
        p.check_invariants()

    @settings(max_examples=200, deadline=None)
    @given(st.lists(st.tuples(st.sampled_from(["alloc", "release", "retain"]),
                              st.integers(1, 5)), max_size=60))
    def test_invariants_under_random_ops(self, ops):
        """ref+cached+free == total after any op sequence; no negative rc."""
        p = BlockPool(16, 4)
        live = []
        for op, n in ops:
            if op == "alloc":
                got = p.allocate(n, PREFIX if n % 2 else TRANSFER)
                if got:
                    if n % 2:
                        p.mark_populated(got)
                    live.append(got)
            elif op == "release" and live:
                p.release(live.pop())
            elif op == "retain" and live:
                p.retain(live[-1])
                p.release(live[-1])
            p.check_invariants()


class TestHybridPrefixCache:
    def test_insert_then_match(self):
        c = make_cache()
        toks = list(range(40))
        assert c.match(toks) == 0
        c.insert(toks)
        assert c.match(toks) == 40       # 10 blocks of 4
        # shorter prefix: full-attn blocks cover it but the linear snapshot
        # exists only at 40 -> hybrid resumable length is 0 (paper §3.2:
        # request-level states reusable only at exact cached length)
        assert c.match(toks[:23]) == 0

    def test_hybrid_requires_both_groups(self):
        """Linear states are request-level: reusable only at their exact
        snapshot length (paper §3.2)."""
        c = make_cache()
        c.insert(list(range(40)))
        # extension of the cached prefix: snapshot at 40 + blocks [0,40)
        assert c.match(list(range(40)) + [99, 98]) == 40
        # shorter prefix: full-attn blocks cover it, but no linear snapshot
        assert c.match(list(range(20))) == 0

    def test_attention_only_partial_match(self):
        c = make_cache(linear=False)
        c.insert(list(range(40)))
        assert c.match(list(range(20))) == 20    # block-level partial hit

    def test_linear_only_exact_match(self):
        c = make_cache(full=False)
        c.insert(list(range(40)))
        assert c.match(list(range(40)) + [7]) == 40
        # snapshots exist only at insert lengths -> shorter prefixes miss
        assert c.match(list(range(36))) == 0
        assert c.match(list(range(28))) == 0

    def test_divergent_suffix_no_match(self):
        c = make_cache()
        c.insert(list(range(40)))
        other = list(range(40))
        other[2] = 999                    # first block differs
        assert c.match(other) == 0

    @settings(max_examples=50, deadline=None)
    @given(st.integers(4, 120), st.integers(0, 119))
    def test_match_never_exceeds_prefix(self, n, cut):
        """Property: match length <= common block-aligned prefix length."""
        c = make_cache(blocks=1024)
        toks = list(np.random.default_rng(0).integers(0, 50, n))
        c.insert(toks)
        cut = min(cut, n)
        query = toks[:cut] + [777]
        m = c.match(query)
        assert m <= cut
        assert m % c.block_tokens == 0

    def test_eviction_under_pressure_keeps_working(self):
        c = make_cache(blocks=16)        # tiny pool
        for i in range(20):
            c.insert(list(range(i * 100, i * 100 + 32)))
        # no crash; pool invariants hold; most old entries evicted
        c.pool.check_invariants()

    def test_transfer_alloc_release(self):
        c = make_cache()
        t = c.allocate_transfer(10)       # 3 blocks of 4 tokens
        assert len(t) == 3
        before = c.pool.free_blocks
        c.release_transfer(t)
        assert c.pool.free_blocks == before + 3


def test_token_block_hashes_chain():
    h1 = token_block_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
    h2 = token_block_hashes([1, 2, 3, 4, 9, 9, 9, 9], 4)
    assert h1[0] == h2[0] and h1[1] != h2[1]
    assert len(token_block_hashes([1, 2, 3], 4)) == 0
