"""Persistent cross-request prefix cache (src/repro/cache/).

Unit layers (blocks / store / policy / facade) plus the system claims:
warm ``prefill_cached`` is bit-identical to cold prefill, and a persistent
cache serves a later flush's shared prefix.
"""

import numpy as np
import pytest

from repro.cache import (PrefixBlockStore, PrefixCache, PrefixCacheConfig,
                         chain_blocks)
from repro.cache.manifest import BlockMeta, CacheGeometry, Manifest
from repro.cache.policy import LRUPinPolicy
from repro.core.offload import IOAccountant, NVME


# --------------------------------------------------------------------------
# blocks: hash chaining
# --------------------------------------------------------------------------

class TestChainBlocks:
    def test_ids_deterministic_and_parent_linked(self):
        toks = np.arange(32)
        a = chain_blocks(toks, 8)
        b = chain_blocks(toks, 8)
        assert [x.block_id for x in a] == [x.block_id for x in b]
        assert a[0].parent_id == "root"
        for prev, cur in zip(a, a[1:]):
            assert cur.parent_id == prev.block_id

    def test_id_pins_down_entire_prefix(self):
        """Same block tokens after a different prefix ⇒ different id."""
        t1 = np.concatenate([np.zeros(8, np.int64), np.arange(8)])
        t2 = np.concatenate([np.ones(8, np.int64), np.arange(8)])
        c1, c2 = chain_blocks(t1, 8), chain_blocks(t2, 8)
        assert c1[1].tokens.tolist() == c2[1].tokens.tolist()
        assert c1[1].block_id != c2[1].block_id

    def test_divergence_keeps_shared_prefix_ids(self):
        base = np.arange(24)
        other = base.copy()
        other[20] = 99                      # diverge inside block 2
        c1, c2 = chain_blocks(base, 8), chain_blocks(other, 8)
        assert c1[0].block_id == c2[0].block_id
        assert c1[1].block_id == c2[1].block_id
        assert c1[2].block_id != c2[2].block_id

    def test_partial_tail_not_chained(self):
        assert len(chain_blocks(np.arange(31), 8)) == 3

    def test_dtype_independent(self):
        toks = np.arange(16)
        assert (chain_blocks(toks.astype(np.int32), 8)[0].block_id
                == chain_blocks(toks.astype(np.int64), 8)[0].block_id)


# --------------------------------------------------------------------------
# store: extent allocator + run-planned reads
# --------------------------------------------------------------------------

def _mk_store(**kw):
    kw.setdefault("n_layers", 2)
    kw.setdefault("capacity_groups", 16)
    kw.setdefault("group_size", 2)
    kw.setdefault("n_kv_heads", 1)
    kw.setdefault("head_dim", 4)
    return PrefixBlockStore(**kw)


def _kv(store, ng, fill):
    shape = (store.n_layers, ng, store.group_size, store.n_kv_heads, store.head_dim)
    k = np.full(shape, float(fill), np.float32)
    return k, -k


class TestPrefixBlockStore:
    def test_alloc_free_merges_extents(self):
        with _mk_store() as st:
            a = st.alloc(4)
            b = st.alloc(4)
            assert (a, b) == (0, 4)
            st.free(a, 4)
            st.free(b, 4)
            assert st.largest_free_extent() == 16   # holes merged back

    def test_alloc_first_fit_and_exhaustion(self):
        with _mk_store(capacity_groups=8) as st:
            a = st.alloc(4); st.alloc(4)
            st.free(a, 4)
            assert st.alloc(2) == 0          # reuses the hole
            assert st.alloc(4) is None       # no contiguous room left

    def test_double_free_raises(self):
        with _mk_store() as st:
            st.alloc(4)
            st.free(0, 4)
            with pytest.raises(RuntimeError):
                st.free(1, 2)

    def test_write_read_roundtrip(self):
        with _mk_store() as st:
            s = st.alloc(3)
            k, v = _kv(st, 3, 7.0)
            st.write_block(s, k, v)
            for layer in range(st.n_layers):
                rk, rv = st.read_extents(layer, [(s, 3)])
                np.testing.assert_array_equal(rk, k[layer])
                np.testing.assert_array_equal(rv, v[layer])

    def test_adjacent_extents_coalesce_to_one_request(self):
        acct = IOAccountant(NVME)
        with _mk_store(accountant=acct) as st:
            s1 = st.alloc(2); s2 = st.alloc(2)       # adjacent
            st.write_block(s1, *_kv(st, 2, 1.0))
            st.write_block(s2, *_kv(st, 2, 2.0))
            acct.reset()
            st.read_extents(0, [(s1, 2), (s2, 2)])
            snap = acct.snapshot()
            assert snap["read_requests"] == 1        # one sequential run
            assert snap["read_bytes"] == 4 * st.group_nbytes

    def test_disjoint_extents_two_requests(self):
        acct = IOAccountant(NVME)
        with _mk_store(accountant=acct) as st:
            st.write_block(st.alloc(2), *_kv(st, 2, 1.0))
            hole = st.alloc(2)
            far = st.alloc(2)
            st.write_block(far, *_kv(st, 2, 2.0))
            st.free(hole, 2)
            acct.reset()
            st.read_extents(0, [(0, 2), (far, 2)])
            assert acct.snapshot()["read_requests"] == 2

    def test_int8_slab_roundtrip_close(self):
        with _mk_store(quant_bits=8) as st:
            assert st.group_nbytes == st.group_size * 2 * 1 * 4  # itemsize 1
            s = st.alloc(2)
            rng = np.random.default_rng(0)
            k = rng.standard_normal((2, 2, 2, 1, 4)).astype(np.float32)
            v = rng.standard_normal((2, 2, 2, 1, 4)).astype(np.float32)
            st.write_block(s, k, v)
            rk, rv = st.read_extents(0, [(s, 2)])
            assert rk.dtype == np.float32
            np.testing.assert_allclose(rk, k[0], atol=0.02)
            np.testing.assert_allclose(rv, v[0], atol=0.02)


# --------------------------------------------------------------------------
# policy: LRU + pins + chain integrity
# --------------------------------------------------------------------------

def _meta(bid, parent, last_used, ng=1, pins=0):
    return BlockMeta(block_id=bid, parent_id=parent, index=0, n_tokens=2 * ng,
                     start_group=0, n_groups=ng, last_used=last_used, pins=pins)


def _manifest(*metas):
    m = Manifest(CacheGeometry(n_layers=1, group_size=2, n_kv_heads=1,
                               head_dim=4, dtype="float32", capacity_groups=16,
                               block_tokens=2))
    for meta in metas:
        m.blocks[meta.block_id] = meta
    return m


class TestLRUPinPolicy:
    def test_lru_order(self):
        m = _manifest(_meta("a", "root", 3), _meta("b", "root", 1),
                      _meta("c", "root", 2))
        v = LRUPinPolicy().victims(m, 2)
        assert [x.block_id for x in v] == ["b", "c"]

    def test_evicting_parent_takes_descendants(self):
        m = _manifest(_meta("a", "root", 1), _meta("b", "a", 5), _meta("c", "b", 6))
        v = LRUPinPolicy().victims(m, 1)
        assert {x.block_id for x in v} == {"a", "b", "c"}

    def test_pin_protects_whole_prefix(self):
        m = _manifest(_meta("a", "root", 1), _meta("b", "a", 2, pins=1),
                      _meta("x", "root", 3))
        v = LRUPinPolicy().victims(m, 1)
        assert [x.block_id for x in v] == ["x"]     # a shielded via pinned b

    def test_all_pinned_returns_none(self):
        m = _manifest(_meta("a", "root", 1, pins=1))
        assert LRUPinPolicy().victims(m, 1) is None


# --------------------------------------------------------------------------
# facade: publish / match / evict / persist
# --------------------------------------------------------------------------

def _open_cache(cache, n_layers=2):
    cache.open(n_layers=n_layers, group_size=2, n_kv_heads=1, head_dim=4,
               dtype=np.float32)
    return cache


def _put_chain(cache, tokens, fill=1.0):
    blocks = chain_blocks(tokens, cache.cfg.block_tokens)
    geo = cache.manifest.geometry
    for blk in blocks:
        ng = blk.n_tokens // geo.group_size
        shape = (geo.n_layers, ng, geo.group_size, geo.n_kv_heads, geo.head_dim)
        k = np.full(shape, fill + blk.index, np.float32)
        assert cache.put_block(blk, k, -k)
    return blocks


class TestPrefixCache:
    def test_longest_prefix_match(self):
        with _open_cache(PrefixCache(PrefixCacheConfig(block_tokens=4))) as c:
            toks = np.arange(16)
            _put_chain(c, toks)
            other = toks.copy()
            other[9] = 99          # diverge in block 2
            assert sum(m.n_tokens for m in c.match(toks)) == 16
            assert sum(m.n_tokens for m in c.match(other)) == 8
            assert c.match(np.arange(100, 116)) == []

    def test_match_max_tokens_cap(self):
        with _open_cache(PrefixCache(PrefixCacheConfig(block_tokens=4))) as c:
            toks = np.arange(16)
            _put_chain(c, toks)
            got = c.match(toks, max_tokens=15)      # whole-prompt hit capped
            assert sum(m.n_tokens for m in got) == 12

    def test_restore_payload_matches_chain_order(self):
        with _open_cache(PrefixCache(PrefixCacheConfig(block_tokens=4))) as c:
            toks = np.arange(12)
            _put_chain(c, toks, fill=5.0)
            metas = c.match(toks)
            k, v = c.read_chain(metas)
            assert k.shape == (2, 12, 1, 4)
            # block i was filled with 5 + i, 4 tokens per block
            want = np.repeat(np.array([5.0, 6.0, 7.0]), 4)
            np.testing.assert_array_equal(k[0, :, 0, 0], want)
            np.testing.assert_array_equal(v[1, :, 0, 0], -want)

    def test_publish_is_idempotent(self):
        with _open_cache(PrefixCache(PrefixCacheConfig(block_tokens=4))) as c:
            toks = np.arange(8)
            _put_chain(c, toks)
            n = c.resident_blocks()
            _put_chain(c, toks)
            assert c.resident_blocks() == n
            assert c.stats.dedup_blocks == n

    def test_eviction_keeps_chains_rooted(self):
        # budget of exactly one chain (4 groups × 2 layers × 64 B/group):
        # each later chain evicts the LRU one, and survivors always include
        # their parents
        cfg = PrefixCacheConfig(block_tokens=4, budget_bytes=4 * 2 * 64)
        with _open_cache(PrefixCache(cfg)) as c:
            assert c.manifest.geometry.capacity_groups == 4
            for base in (0, 100, 200):
                _put_chain(c, np.arange(base, base + 8))
                for meta in c.manifest.blocks.values():
                    assert (meta.parent_id == "root"
                            or meta.parent_id in c.manifest.blocks)
            assert c.stats.evicted_blocks > 0
            # the latest chain is resident, the first is gone
            assert sum(m.n_tokens for m in c.match(np.arange(200, 208))) == 8
            assert c.match(np.arange(0, 8)) == []

    def test_pinned_blocks_never_evicted(self):
        cfg = PrefixCacheConfig(block_tokens=4, budget_bytes=4 * 2 * 64)
        with _open_cache(PrefixCache(cfg)) as c:
            pinned = _put_chain(c, np.arange(8))
            metas = c.match(np.arange(8))
            c.pin(metas)
            assert not c.put_block(
                chain_blocks(np.arange(50, 58), 4)[0],
                np.zeros((2, 1, 2, 1, 4), np.float32),
                np.zeros((2, 1, 2, 1, 4), np.float32))
            assert c.stats.declined_blocks == 1
            for blk in pinned:
                assert c.contains(blk.block_id)
            c.unpin(metas)

    def test_persistence_roundtrip(self, tmp_path):
        d = str(tmp_path / "cache")
        toks = np.arange(12)
        cfg = PrefixCacheConfig(block_tokens=4, dir=d)
        with _open_cache(PrefixCache(cfg)) as c:
            _put_chain(c, toks, fill=3.0)
        with _open_cache(PrefixCache(cfg)) as c2:
            metas = c2.match(toks)
            assert sum(m.n_tokens for m in metas) == 12
            k, _ = c2.read_chain(metas)
            np.testing.assert_array_equal(
                k[0, :, 0, 0], np.repeat(np.array([3.0, 4.0, 5.0]), 4))
            # reopened slab must not hand out occupied extents
            assert c2.store.free_groups() == c2.manifest.geometry.capacity_groups - 6

    def test_geometry_mismatch_raises(self, tmp_path):
        d = str(tmp_path / "cache")
        with _open_cache(PrefixCache(PrefixCacheConfig(block_tokens=4, dir=d))):
            pass
        with pytest.raises(ValueError, match="geometry mismatch"):
            PrefixCache(PrefixCacheConfig(block_tokens=4, dir=d)).open(
                n_layers=3, group_size=2, n_kv_heads=1, head_dim=4,
                dtype=np.float32)

    def test_block_tokens_must_align_to_groups(self):
        with pytest.raises(ValueError, match="multiple of"):
            PrefixCache(PrefixCacheConfig(block_tokens=5)).open(
                n_layers=1, group_size=2, n_kv_heads=1, head_dim=4,
                dtype=np.float32)

    def test_peek_agrees_with_match(self):
        with _open_cache(PrefixCache(PrefixCacheConfig(block_tokens=4))) as c:
            toks = np.arange(16)
            _put_chain(c, toks)
            other = toks.copy()
            other[9] = 99
            assert c.peek(toks) == 16
            assert c.peek(other) == 8
            assert c.peek(np.arange(100, 116)) == 0

    def test_peek_is_observably_side_effect_free(self):
        """peek() must not move LRU order, mutate stats, pin, or charge
        I/O — the router scores every replica per submission, and a
        scoring pass that perturbed eviction order would make routing
        observable in cache behavior."""
        import dataclasses as _dc

        acct = IOAccountant(NVME)
        with _open_cache(PrefixCache(PrefixCacheConfig(block_tokens=4),
                                     accountant=acct)) as c:
            toks = np.arange(16)
            _put_chain(c, toks)
            before_stats = _dc.asdict(c.stats)
            before_lru = {bid: m.last_used
                          for bid, m in c.manifest.blocks.items()}
            before_io = acct.snapshot()
            for _ in range(3):
                c.peek(toks)
                c.peek(np.arange(50, 66))
            assert _dc.asdict(c.stats) == before_stats
            assert {bid: m.last_used
                    for bid, m in c.manifest.blocks.items()} == before_lru
            assert all(m.pins == 0 for m in c.manifest.blocks.values())
            assert acct.snapshot() == before_io

    def test_peek_on_unopened_cache_is_zero(self):
        assert PrefixCache(PrefixCacheConfig(block_tokens=4)).peek(
            np.arange(8)) == 0


# --------------------------------------------------------------------------
# engine integration: the acceptance claims
# --------------------------------------------------------------------------

@pytest.fixture()
def tiny_engine_parts(tiny_cfg, tiny_params, tiny_adapter):
    from repro.core.engine import EngineConfig

    rng = np.random.default_rng(3)
    calib = rng.standard_normal((256, tiny_cfg.n_kv_heads, tiny_cfg.head_dim))
    ecfg = EngineConfig(group_size=4, n_select=8, rank=8, reuse_capacity=16,
                        max_seq=128)
    return tiny_cfg, tiny_params, tiny_adapter, ecfg, calib, rng


def _engine(parts, **overrides):
    import dataclasses

    from repro.core.engine import KVSwapEngine

    cfg, params, adapter, ecfg, calib, _ = parts
    if overrides:
        ecfg = dataclasses.replace(ecfg, **overrides)
    return KVSwapEngine(adapter, params, ecfg, batch=2, calib_k=calib)


class TestEnginePrefixCache:
    def test_warm_prefill_bit_identical(self, tiny_engine_parts):
        """Acceptance: fully cached prefix ⇒ bit-identical next-token logits,
        and the decode that follows stays bit-identical too."""
        rng = tiny_engine_parts[-1]
        prompt = rng.integers(0, 97, (2, 37)).astype(np.int32)
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            with _engine(tiny_engine_parts) as cold:
                lc = np.asarray(cold.prefill(prompt))
                cold.publish(cache)
                cold_steps = [np.asarray(cold.decode_step(np.full(2, t)))
                              for t in (5, 9, 13)]
            with _engine(tiny_engine_parts) as warm:
                lw = np.asarray(warm.prefill_cached(prompt, cache))
                assert warm.prefill_report["cached_tokens"] == 32
                warm_steps = [np.asarray(warm.decode_step(np.full(2, t)))
                              for t in (5, 9, 13)]
        np.testing.assert_array_equal(lc, lw)
        for a, b in zip(cold_steps, warm_steps):
            np.testing.assert_array_equal(a, b)

    def test_fully_cached_prompt_still_recomputes_tail(self, tiny_engine_parts):
        """Prompt length divisible by block_tokens and fully published: the
        match is capped so ≥ 1 token is recomputed and logits still emerge."""
        rng = tiny_engine_parts[-1]
        prompt = rng.integers(0, 97, (2, 32)).astype(np.int32)
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            with _engine(tiny_engine_parts) as cold:
                lc = np.asarray(cold.prefill(prompt))
                cold.publish(cache)
            with _engine(tiny_engine_parts) as warm:
                lw = np.asarray(warm.prefill_cached(prompt, cache))
                assert warm.prefill_report["cached_tokens"] == 24
        np.testing.assert_array_equal(lc, lw)

    def test_unrelated_prompt_falls_back_cold(self, tiny_engine_parts):
        rng = tiny_engine_parts[-1]
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            p1 = rng.integers(0, 97, (2, 24)).astype(np.int32)
            with _engine(tiny_engine_parts) as e1:
                e1.prefill(p1)
                e1.publish(cache)
            p2 = rng.integers(0, 97, (2, 24)).astype(np.int32)
            with _engine(tiny_engine_parts) as e2:
                cold_direct = np.asarray(_ref_prefill(tiny_engine_parts, p2))
                lw = np.asarray(e2.prefill_cached(p2, cache))
                assert e2.prefill_report["cached_tokens"] == 0
        np.testing.assert_array_equal(cold_direct, lw)

    def test_publish_dedups_across_engines(self, tiny_engine_parts):
        rng = tiny_engine_parts[-1]
        prompt = rng.integers(0, 97, (2, 24)).astype(np.int32)
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            with _engine(tiny_engine_parts) as e1:
                e1.prefill(prompt)
                # rows are identical? no — rows differ, but re-publishing the
                # same engine twice must add nothing new
                n1 = e1.publish(cache)
                assert e1.publish(cache) == 0
            assert n1 == cache.resident_blocks()

    def test_restore_reads_are_sequential_runs(self, tiny_engine_parts):
        """Restore I/O: one coalesced request per (layer, row-chain), not one
        per group — and charged to the engine accountant."""
        rng = tiny_engine_parts[-1]
        # two distinct rows → two chains; tiny model has 2 KV layers
        prompt = rng.integers(0, 97, (2, 32)).astype(np.int32)
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            with _engine(tiny_engine_parts) as e1:
                e1.prefill(prompt)
                e1.publish(cache)
            with _engine(tiny_engine_parts) as e2:
                e2.accountant.reset()
                e2.prefill_cached(prompt, cache)
                rep = e2.prefill_report
                assert rep["cached_tokens"] == 24
                assert rep["restore_seconds"] > 0
                snap = e2.accountant.snapshot()
                # 24 cached tokens = 3 blocks/row published contiguously per
                # chain ⇒ 1 run per (layer, chain): 2 layers × 2 chains
                assert snap["read_requests"] == 4

    def test_failed_restore_unpins_on_every_path(self, tiny_engine_parts):
        """Regression for the pin-leak hazard in the restore discipline:
        a storage fault that escapes ``read_chain`` mid-restore must not
        leave matched blocks pinned — a leaked pin makes the block
        unevictable forever (LRUPinPolicy never victimizes pinned
        blocks) and would silently shrink the budget with every failed
        admission.  The cache must stay fully usable afterwards."""
        from repro.faults.errors import MediaError, StorageFault

        rng = tiny_engine_parts[-1]
        prompt = rng.integers(0, 97, (2, 37)).astype(np.int32)
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            with _engine(tiny_engine_parts) as e1:
                e1.prefill(prompt)
                e1.publish(cache)
            with _engine(tiny_engine_parts) as e2:
                orig = cache.store.read_extents

                def boom(*a, **kw):
                    raise MediaError("injected: extent unreadable")

                cache.store.read_extents = boom
                try:
                    with pytest.raises(StorageFault):
                        e2.admit_row(0, prompt[0], cache)
                finally:
                    cache.store.read_extents = orig
                assert all(m.pins == 0
                           for m in cache.manifest.blocks.values())
                # the same admission now restores warm — nothing was
                # quarantined, evicted, or left half-admitted
                e2.admit_row(0, prompt[0], cache)
                assert e2.prefill_report["cached_tokens"] > 0
                e2.retire_row(0)
                assert all(m.pins == 0
                           for m in cache.manifest.blocks.values())

    def test_hybrid_model_falls_back(self, rng):
        import jax

        from repro.core.engine import EngineConfig, KVSwapEngine
        from repro.models.transformer import (ModelConfig, TransformerAdapter,
                                              init_params)

        cfg = ModelConfig(name="hyb", arch_type="hybrid", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128,
                          vocab_size=61, block_pattern=("mamba2", "shared_attn"),
                          ssm_state=16)
        params = init_params(jax.random.PRNGKey(1), cfg)
        calib = rng.standard_normal((128, 4, 16))
        ecfg = EngineConfig(group_size=4, n_select=8, rank=8, reuse_capacity=8,
                            max_seq=64)
        prompt = rng.integers(0, 61, (2, 17)).astype(np.int32)
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            with KVSwapEngine(TransformerAdapter(cfg), params, ecfg, batch=2,
                              calib_k=calib) as eng:
                logits = eng.prefill_cached(prompt, cache)
                assert logits.shape == (2, 61)
                assert eng.prefill_report["cached_tokens"] == 0
                assert eng.publish(cache) == 0


def _ref_prefill(parts, prompt):
    with _engine(parts) as e:
        return e.prefill(prompt)


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

class TestServingIntegration:
    def test_batch_server_session_hit_rate(self, tiny_cfg, tiny_params,
                                           tiny_adapter, rng):
        from repro.core.engine import EngineConfig
        from repro.serving.scheduler import BatchServer

        calib = rng.standard_normal((128, tiny_cfg.n_kv_heads, tiny_cfg.head_dim))
        ecfg = EngineConfig(group_size=4, n_select=24, rank=16,
                            reuse_capacity=24, max_seq=96, predict_from="self")
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            srv = BatchServer(tiny_adapter, tiny_params, ecfg, batch=2,
                              calib_k=calib, prefix_cache=cache)
            sys_prompt = rng.integers(0, tiny_cfg.vocab_size, 24)

            def turn(extra):
                return np.concatenate(
                    [sys_prompt, rng.integers(0, tiny_cfg.vocab_size, extra)])

            srv.submit(turn(6), max_new=4)
            srv.submit(turn(6), max_new=4)          # flush 1, cold
            s1 = srv.last_stats
            assert s1["prefix_cache"]["hit_rate"] == 0.0
            assert s1["real_requests"] == 2

            srv.submit(turn(8), max_new=4)
            srv.submit(turn(8), max_new=4)          # flush 2, warm
            s2 = srv.last_stats
            assert s2["prefix_cache"]["hit_rate"] >= 0.5
            assert s2["prefix_cache"]["saved_prefill_tokens"] > 0
            assert s2["prefill"]["cached_tokens"] >= 16

    def test_padded_flush_excludes_pads_from_throughput(self, tiny_cfg,
                                                        tiny_params,
                                                        tiny_adapter, rng):
        from repro.core.engine import EngineConfig
        from repro.serving.scheduler import BatchServer

        calib = rng.standard_normal((128, tiny_cfg.n_kv_heads, tiny_cfg.head_dim))
        ecfg = EngineConfig(group_size=4, n_select=16, rank=16,
                            reuse_capacity=16, max_seq=96, predict_from="self")
        srv = BatchServer(tiny_adapter, tiny_params, ecfg, batch=2, calib_k=calib)
        srv.submit(rng.integers(0, tiny_cfg.vocab_size, 20), max_new=3)
        srv.flush()                                  # 1 real + 1 pad row
        st = srv.last_stats
        assert (st["real_requests"], st["padded_requests"]) == (1, 1)
        assert st["throughput"] == pytest.approx(st["batch_throughput"] / 2)
