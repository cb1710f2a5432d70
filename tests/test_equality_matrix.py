"""Authoritative cross-feature bit-identity matrix.

One parametrized sweep asserting greedy tokens are **bit-identical** across
``device_resident`` × ``async_io`` × ``kv_bits`` × warm tier × prefix-cache
restore × Pallas — the single grid that replaces the ad-hoc pairwise checks
scattered across test_hotpath / test_warm_tier / test_serving_api (those
remain, marked ``slow``).

The exact-equality lattice being pinned:

* ``device_resident``, ``async_io``, ``use_pallas`` are pure execution-path
  knobs: bit-identical at **any** ``kv_bits``;
* the warm tier is bit-exact only at ``kv_bits=8`` (admission re-quantizes
  with the on-disk scale, so a hit returns the exact disk bytes);
* the prefix cache stores the raw engine dtype at its default
  ``kv_bits=16``: restores are bit-exact against the kv16 reference;
* therefore every combo compares against the cached sync/host/featureless
  reference **of its own kv_bits** — kv16 vs kv8 tokens may legitimately
  differ (int8 disk tier quantizes), and that boundary is the contract.

Every combo drives the full continuous-batching ServeSession (2 slots, two
concurrent requests), so the grid also covers the serving admission /
retirement machinery, not just the static engine.
"""

import numpy as np
import pytest

from repro.core.engine import EngineConfig
from repro.serving.api import ServeSession

BASE = dict(group_size=4, n_select=6, rank=8, reuse_capacity=4, max_seq=128,
            predict_from="self")
HEAD = 32            # published/restored prefix length (4 cache blocks)
MAX_NEW = 8
WARM_BUDGET = 1 << 20


def make_cfg(**kw) -> EngineConfig:
    return EngineConfig(**{**BASE, **kw})


def combos() -> list:
    """The grid: for every (device_resident × async_io) execution pair,
    each feature that must preserve tokens at its exact-equality kv_bits."""
    out = []
    for dr in (False, True):
        for aio in (False, True):
            # (kv_bits, warm, prefix, pallas)
            out += [
                (dr, aio, 16, False, False, False),   # kv16 plain
                (dr, aio, 16, False, True, False),    # kv16 + prefix restore
                (dr, aio, 16, False, False, True),    # kv16 + pallas
                (dr, aio, 8, False, False, False),    # kv8 plain
                (dr, aio, 8, True, False, False),     # kv8 + warm tier
            ]
    return out


def combo_id(c) -> str:
    dr, aio, kvb, warm, prefix, pallas = c
    return (f"dr{int(dr)}-aio{int(aio)}-kv{kvb}-warm{int(warm)}"
            f"-px{int(prefix)}-pl{int(pallas)}")


@pytest.fixture(scope="module")
def setup(tiny_cfg, tiny_params, tiny_adapter):
    rng = np.random.default_rng(42)
    calib = rng.standard_normal(
        (256, tiny_cfg.n_kv_heads, tiny_cfg.head_dim)).astype(np.float32)
    # long enough that reuse_capacity=4 < n_select=6 forces evictions and
    # re-reads (warm-tier traffic); distinct heads so each prompt restores
    # its own published prefix
    prompts = [rng.integers(0, tiny_cfg.vocab_size, 57),
               rng.integers(0, tiny_cfg.vocab_size, 49)]
    return tiny_cfg, tiny_params, tiny_adapter, calib, prompts


def run_combo(setup, dr, aio, kvb, warm, prefix, pallas) -> list[np.ndarray]:
    cfg, params, adapter, calib, prompts = setup
    ecfg = make_cfg(device_resident=dr, async_io=aio, kv_bits=kvb,
                    warm_budget_bytes=WARM_BUDGET if warm else 0,
                    use_pallas=pallas)

    def session(cache=None):
        return ServeSession(adapter, params, ecfg, slots=2, calib_k=calib,
                            prefix_cache=cache)

    if prefix:
        from repro.cache import PrefixCache, PrefixCacheConfig

        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            with session(cache) as sess:
                for p in prompts:          # publish each prompt's head
                    sess.submit(p[:HEAD], 1)
                sess.drain()
                rids = [sess.submit(p, MAX_NEW) for p in prompts]
                done = sess.drain()
                for r in rids:             # the restore path actually ran
                    assert done[r].cached_tokens >= HEAD - 8
                return [done[r].output for r in rids]
    with session() as sess:
        rids = [sess.submit(p, MAX_NEW) for p in prompts]
        done = sess.drain()
        if warm:                           # the warm tier actually served
            assert sess.engine.warm.stats.hits > 0
        return [done[r].output for r in rids]


# per-kv_bits reference tokens: sync, host-gather, featureless — computed
# once per module run and shared by every combo of that kv_bits
_REFS: dict[int, list[np.ndarray]] = {}


def reference(setup, kvb) -> list[np.ndarray]:
    if kvb not in _REFS:
        _REFS[kvb] = run_combo(setup, False, False, kvb,
                               False, False, False)
    return _REFS[kvb]


class TestEqualityMatrix:
    @pytest.mark.parametrize("combo", combos(), ids=combo_id)
    def test_tokens_bit_identical_to_reference(self, setup, combo):
        dr, aio, kvb, warm, prefix, pallas = combo
        ref = reference(setup, kvb)
        outs = run_combo(setup, dr, aio, kvb, warm, prefix, pallas)
        for got, want in zip(outs, ref):
            np.testing.assert_array_equal(got, want)

    def test_matrix_covers_required_grid(self):
        """The acceptance floor: >= 16 combos in the default CI job, and
        every axis of the feature lattice actually varies."""
        cs = combos()
        assert len(cs) >= 16
        assert len(set(cs)) == len(cs)
        for axis in range(6):
            assert len({c[axis] for c in cs}) == 2

    def test_legacy_fetch_equals_tier_chain(self, setup):
        """The tier-chain refactor is a pure re-plumbing: ``fetch`` through
        the ordered ``KVTier`` chain must produce bit-identical tokens to
        the pre-refactor hand-inlined path (warm per-group serve loop, then
        ReadScheduler plan + retrying run reads) across
        device_resident × async_io × kv_bits.  The legacy path is
        reimplemented verbatim below and bound over each manager, so the
        comparison holds even as the chain walker evolves.
        """
        import types

        from repro.core.manager import MappingTable

        def legacy_fetch(self, group_ids, group_mask):
            # pre-refactor body (commit bd149e8), minus the obs plan
            # counters that moved into DiskTier
            b, m = group_ids.shape
            slots = np.full((b, m), -1, dtype=np.int64)
            ids_out = np.where(group_mask, group_ids, -1)
            staged, new_groups = {}, []
            for bi in range(b):
                want = [int(g) for g, ok
                        in zip(group_ids[bi], group_mask[bi]) if ok]
                want = list(dict.fromkeys(want))
                want_set = set(want)
                _, misses = self.reuse.lookup(bi, want)
                if self.warm is not None and misses:
                    disk_misses = []
                    for gid in misses:
                        kv_flat = self.warm.serve(self.layer, bi, gid,
                                                  self.store.dtype)
                        if kv_flat is None:
                            disk_misses.append(gid)
                            continue
                        slot = self.reuse.insert(bi, gid, kv_flat,
                                                 protected=want_set)
                        if slot is None:
                            staged[(bi, gid)] = kv_flat
                        else:
                            new_groups.append((bi, slot, kv_flat))
                    misses = disk_misses
                for run in self.scheduler.plan(misses):
                    k_r, v_r = self.read_run_with_retry(bi, run)
                    for gid in run.ids:
                        off = gid - run.start
                        kv = np.stack([k_r[off], v_r[off]], axis=1)
                        slot = self.reuse.insert(bi, gid, kv,
                                                 protected=want_set)
                        if slot is None:
                            staged[(bi, gid)] = kv
                        else:
                            new_groups.append((bi, slot, kv))
                for mi in range(m):
                    if group_mask[bi, mi]:
                        gid = int(group_ids[bi, mi])
                        slot = self.reuse.slot_of(bi, gid)
                        slots[bi, mi] = -2 if slot is None else slot
            return MappingTable(
                group_ids=ids_out, slots=slots,
                group_mask=np.asarray(group_mask, bool),
                rolling_fill=self.rolling.fills.copy(), staged=staged,
                new_groups=new_groups)

        cfg, params, adapter, calib, prompts = setup
        for dr in (False, True):
            for aio in (False, True):
                for kvb in (16, 8):
                    # warm tier on at kv8 so the legacy warm-serve branch
                    # actually runs (bit-exact regime)
                    ecfg = make_cfg(device_resident=dr, async_io=aio,
                                    kv_bits=kvb,
                                    warm_budget_bytes=WARM_BUDGET
                                    if kvb == 8 else 0)

                    def run(patch_legacy):
                        with ServeSession(adapter, params, ecfg, slots=2,
                                          calib_k=calib) as sess:
                            if patch_legacy:
                                for mgr in sess.engine.managers:
                                    mgr.fetch = types.MethodType(
                                        legacy_fetch, mgr)
                            rids = [sess.submit(p, MAX_NEW) for p in prompts]
                            done = sess.drain()
                            return [done[r].output for r in rids]

                    for got, want in zip(run(False), run(True)):
                        np.testing.assert_array_equal(
                            got, want,
                            err_msg=f"dr={dr} aio={aio} kv={kvb}")

    def test_kv_bits_references_are_distinct_tiers(self, setup):
        """Guard against the matrix silently collapsing: the per-kv_bits
        reference split exists because the int8 disk tier is a different
        on-disk format.  Prove the formats genuinely differ — the kv8
        baseline must move ~4x fewer disk-read bytes than the kv16 one
        (tokens themselves may or may not coincide on a tiny model)."""
        cfg, params, adapter, calib, prompts = setup
        read = {}
        for kvb in (16, 8):
            with ServeSession(adapter, params, make_cfg(kv_bits=kvb),
                              slots=2, calib_k=calib) as sess:
                for p in prompts:
                    sess.submit(p, MAX_NEW)
                sess.drain()
                read[kvb] = sess.stats()["read_bytes"]
        assert 0 < read[8] < read[16]
