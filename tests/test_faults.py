"""Storage fault injection + graceful degradation (docs/robustness.md).

Covers the fault package itself (deterministic plans, fake-clock retry),
the recovery machinery it exercises (checksummed prefix blocks, manifest
recovery, worker survival), and the session-level degradation ladder
(per-request FAILED isolation, survivor replay, load shedding).
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from repro.cache import PrefixCache, PrefixCacheConfig
from repro.cache.manifest import Manifest
from repro.core.engine import EngineConfig, KVSwapEngine
from repro.core.offload import DISKS, IOAccountant, KVDiskStore
from repro.faults import (FaultPlan, FaultSpec, FaultyDisk, RetryPolicy,
                          call_with_retries)
from repro.faults.errors import (CorruptBlockError, FetchFailed,
                                 InjectedCrash, ManifestCorrupt, MediaError,
                                 RetriesExhausted, TornReadError,
                                 TransientReadError)
from repro.io import PrefetchWorker
from repro.serving.api import DONE, FAILED, DegradationPolicy, ServeSession
from repro.serving.errors import RequestRejected


# shadow the session-scoped conftest rng: this module must not consume
# draws from the shared stream (statistical tests later in the suite
# depend on its exact position)
@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


def make_ecfg(**kw):
    base = dict(group_size=4, n_select=6, rank=8, reuse_capacity=12,
                max_seq=128, predict_from="self")
    base.update(kw)
    return EngineConfig(**base)


@pytest.fixture(scope="module")
def parts(tiny_cfg, tiny_params, tiny_adapter, rng):
    calib = rng.standard_normal(
        (256, tiny_cfg.n_kv_heads, tiny_cfg.head_dim)).astype(np.float32)
    return tiny_cfg, tiny_params, tiny_adapter, calib


def make_engine(parts, batch=2, faults=None, **overrides):
    cfg, params, adapter, calib = parts
    return KVSwapEngine(adapter, params, make_ecfg(**overrides), batch=batch,
                        calib_k=calib, faults=faults)


def make_session(parts, slots=2, **kw):
    cfg, params, adapter, calib = parts
    ecfg = kw.pop("ecfg", make_ecfg())
    return ServeSession(adapter, params, ecfg, slots=slots, calib_k=calib,
                        **kw)


# --------------------------------------------------------------------------
# retry policy: fake clock, no real sleeps anywhere
# --------------------------------------------------------------------------

class TestRetryPolicy:
    def test_backoff_sequence_is_deterministic_exponential(self):
        pol = RetryPolicy(max_attempts=6, backoff_base_s=0.002,
                          backoff_mult=2.0, backoff_max_s=0.01)
        assert [pol.backoff(i) for i in range(1, 6)] == \
            [0.002, 0.004, 0.008, 0.01, 0.01]

    def test_transient_retried_then_succeeds(self):
        calls, delays = [], []
        def fn():
            calls.append(1)
            if len(calls) < 3:
                raise TransientReadError("flaky")
            return 42
        got = call_with_retries(fn, policy=RetryPolicy(max_attempts=3),
                                on_backoff=delays.append)
        assert got == 42 and len(calls) == 3
        assert delays == [0.002, 0.004]

    def test_exhausted_escalates_with_cause_and_attempts(self):
        def fn():
            raise TornReadError("short read")
        with pytest.raises(RetriesExhausted) as ei:
            call_with_retries(fn, policy=RetryPolicy(max_attempts=3))
        assert ei.value.attempts == 3
        assert isinstance(ei.value.__cause__, TornReadError)

    def test_persistent_fault_not_retried(self):
        calls = []
        def fn():
            calls.append(1)
            raise MediaError("dead extent")
        with pytest.raises(MediaError):
            call_with_retries(fn, policy=RetryPolicy(max_attempts=5))
        assert len(calls) == 1

    def test_deadline_on_injected_clock(self):
        """Deadline enforcement runs entirely on a fake clock the backoff
        hook advances — wall time never moves."""
        t = [0.0]
        def fn():
            raise TransientReadError("flaky")
        def on_backoff(delay):
            t[0] += delay
        with pytest.raises(RetriesExhausted) as ei:
            call_with_retries(
                fn, policy=RetryPolicy(max_attempts=100, deadline_s=0.005),
                on_backoff=on_backoff, clock=lambda: t[0])
        # failures 1-2 backoff 0.002+0.004 = 0.006 >= deadline at failure 3
        assert ei.value.attempts == 3
        assert ei.value.deadline_s == 0.005


# --------------------------------------------------------------------------
# fault plan: determinism, burst semantics, write-born persistence
# --------------------------------------------------------------------------

def _probe(plan, n=24):
    """Outcome trace of a fixed op grid: fault class name or stall."""
    out = []
    for i in range(n):
        try:
            out.append(plan.on_read(i % 2, i % 3, 4 * i, 4, disk="emmc"))
        except Exception as exc:  # noqa: BLE001 — recording, not handling
            out.append(type(exc).__name__)
    return out

class TestFaultPlan:
    SPEC = FaultSpec(seed=7, read_error_rate=0.3, torn_read_rate=0.2,
                     spike_rate=0.3, spike_seconds=0.004)

    def test_same_spec_same_fault_pattern(self):
        a, b = _probe(FaultPlan(self.SPEC)), _probe(FaultPlan(self.SPEC))
        assert a == b
        assert any(x == "TransientReadError" for x in a)  # campaign is live

    def test_different_seed_different_pattern(self):
        other = dataclasses.replace(self.SPEC, seed=8)
        assert _probe(FaultPlan(self.SPEC)) != _probe(FaultPlan(other))

    def test_burst_fails_exactly_burst_attempts_then_succeeds(self):
        plan = FaultPlan(FaultSpec(seed=0, read_error_rate=1.0, error_burst=2))
        for _ in range(2):
            with pytest.raises(TransientReadError):
                plan.on_read(0, 0, 0, 4)
        assert plan.on_read(0, 0, 0, 4) == 0.0   # burst spent: attempt 3 ok
        # rate 1.0 ⇒ the NEXT occurrence of the same logical op re-arms
        with pytest.raises(TransientReadError):
            plan.on_read(0, 0, 0, 4)

    def test_burst_below_retry_budget_always_recovers(self):
        """The bit-identity configuration: burst < max_attempts ⇒ every
        logical read eventually succeeds inside its retry loop."""
        plan = FaultPlan(FaultSpec(seed=1, read_error_rate=0.8, error_burst=2))
        for op in range(50):
            got = call_with_retries(
                lambda op=op: plan.on_read(0, 0, 4 * op, 4),
                policy=RetryPolicy(max_attempts=3))
            assert got == 0.0

    def test_bad_extents_born_at_write_cleared_by_rewrite(self):
        plan = FaultPlan(FaultSpec(seed=3, bad_extent_rate=1.0))
        plan.on_write(0, 1, 0, 8)
        (layer, row, gid), = plan.bad_extents()
        assert (layer, row) == (0, 1) and 0 <= gid < 8
        with pytest.raises(MediaError):
            plan.on_read(layer, row, gid, 1)
        # a rewrite of the covering extent remaps: old mark gone, new draw
        plan.on_write(0, 1, 0, 8)
        assert len(plan.bad_extents()) == 1
        plan2 = FaultPlan(FaultSpec(seed=3, bad_extent_rate=0.0))
        plan2.on_write(0, 1, 0, 8)
        assert plan2.bad_extents() == set()

    def test_crash_point_fires_exactly_once(self):
        plan = FaultPlan(FaultSpec(crash_points=("manifest_write",)))
        assert plan.should_crash("manifest_write")
        assert not plan.should_crash("manifest_write")
        assert not plan.should_crash("other_site")
        assert plan.snapshot()["crashes"] == 1


# --------------------------------------------------------------------------
# FaultyDisk: wrapper semantics over a real KVDiskStore
# --------------------------------------------------------------------------

def _disk_store(disk="emmc"):
    acc = IOAccountant(DISKS[disk])
    store = KVDiskStore(n_layers=2, batch=1, max_groups=8, group_size=4,
                        n_kv_heads=2, head_dim=8, accountant=acc)
    k = np.random.default_rng(0).standard_normal((2, 32, 2, 8)) \
        .astype(np.float32)
    for j in range(2):
        store.write_prefill_row(j, 0, k[j], k[j])
    return store, acc

class TestFaultyDisk:
    def test_spike_charges_modeled_stall_not_wall(self):
        store, acc = _disk_store("emmc")
        plan = FaultPlan(FaultSpec(seed=0, spike_rate=1.0, spike_seconds=0.004))
        fd = FaultyDisk(store, plan)
        before = acc.snapshot()
        k, v = fd.read_run(0, 0, 0, 4)
        after = acc.snapshot()
        assert after["stall_seconds"] == pytest.approx(0.004)
        # the spike lands INSIDE read_seconds: every io_seconds consumer
        # (StepStats, SLO math) sees it without new plumbing
        assert after["read_seconds"] - before["read_seconds"] > 0.004
        np.testing.assert_array_equal(k, store.read_run(0, 0, 0, 4)[0])

    def test_spikes_only_fire_on_configured_disks(self):
        store, acc = _disk_store("nvme")
        fd = FaultyDisk(store, FaultPlan(
            FaultSpec(seed=0, spike_rate=1.0, spike_seconds=0.004)))
        fd.read_run(0, 0, 0, 4)
        assert acc.snapshot()["stall_seconds"] == 0.0

    def test_write_born_bad_extent_raises_media_error(self):
        store, _ = _disk_store()
        plan = FaultPlan(FaultSpec(seed=3, bad_extent_rate=1.0))
        fd = FaultyDisk(store, plan)
        k = np.zeros((2, 8, 2, 8), np.float32)
        fd.write_prefill_row(0, 0, k[0], k[0])
        (layer, row, gid), = plan.bad_extents()
        with pytest.raises(MediaError):
            fd.read_run(layer, row, gid, 1)

    def test_payload_identical_when_no_fault_fires(self):
        store, _ = _disk_store()
        fd = FaultyDisk(store, FaultPlan(FaultSpec()))
        k, v = fd.read_run(1, 0, 2, 3)
        k0, v0 = store.read_run(1, 0, 2, 3)
        np.testing.assert_array_equal(k, k0)
        np.testing.assert_array_equal(v, v0)

    def test_attribute_delegation_both_ways(self):
        store, acc = _disk_store()
        fd = FaultyDisk(store, FaultPlan(FaultSpec()))
        assert fd.group_nbytes == store.group_nbytes
        fd.warm = None          # engine does this post-construction
        assert store.warm is None


# --------------------------------------------------------------------------
# manifest durability + prefix-cache directory recovery
# --------------------------------------------------------------------------

class TestManifestRecovery:
    GEO = dict(n_layers=2, group_size=4, n_kv_heads=2, head_dim=8,
               dtype="float32")

    def test_load_of_torn_json_is_typed(self, tmp_path):
        p = tmp_path / "manifest.json"
        p.write_text('{"geometry": {"n_layers": ')
        with pytest.raises(ManifestCorrupt):
            Manifest.load(str(p))

    def test_load_of_garbage_payload_is_typed(self, tmp_path):
        p = tmp_path / "manifest.json"
        p.write_text(json.dumps({"geometry": {"bogus": 1}, "blocks": []}))
        with pytest.raises(ManifestCorrupt):
            Manifest.load(str(p))

    def test_cache_recovers_torn_dir_and_gcs_orphans(self, tmp_path):
        d = str(tmp_path / "cache")
        os.makedirs(d)
        with open(os.path.join(d, "manifest.json"), "w") as f:
            f.write('{"geometry": {"n_l')       # torn mid-write
        with open(os.path.join(d, "blocks.bin"), "wb") as f:
            f.write(b"\0" * 4096)               # orphaned slab
        cache = PrefixCache(PrefixCacheConfig(block_tokens=8, dir=d))
        assert cache.recovered_from is not None
        assert not os.path.exists(os.path.join(d, "blocks.bin"))
        # the recovered directory is fully usable: open + save a new index
        cache.open(**self.GEO)
        cache.save()
        assert PrefixCache(PrefixCacheConfig(block_tokens=8, dir=d)) \
            .recovered_from is None

    def test_crash_point_leaves_torn_manifest_next_open_recovers(self,
                                                                 tmp_path):
        d = str(tmp_path / "cache")
        cache = PrefixCache(PrefixCacheConfig(block_tokens=8, dir=d))
        cache.open(**self.GEO)
        cache.use_faults(FaultPlan(FaultSpec(crash_points=("manifest_write",))))
        with pytest.raises(InjectedCrash):
            cache.save()
        with pytest.raises(ManifestCorrupt):
            Manifest.load(os.path.join(d, "manifest.json"))
        re = PrefixCache(PrefixCacheConfig(block_tokens=8, dir=d))
        assert re.recovered_from is not None
        re.open(**self.GEO)                     # usable again
        re.save()                               # crash point already spent

    def test_save_then_load_roundtrips_checksums(self, tmp_path):
        d = str(tmp_path / "cache")
        cache = PrefixCache(PrefixCacheConfig(block_tokens=8, dir=d))
        cache.open(**self.GEO)
        from repro.cache import chain_blocks
        blk = chain_blocks(np.arange(8), 8)[0]
        k = np.random.default_rng(0).standard_normal((2, 2, 4, 2, 8)) \
            .astype(np.float32)
        assert cache.put_block(blk, k, k)
        crc = cache.manifest.blocks[blk.block_id].checksum
        assert crc != 0
        cache.save()
        re = Manifest.load(os.path.join(d, "manifest.json"))
        assert re.blocks[blk.block_id].checksum == crc


# --------------------------------------------------------------------------
# checksummed prefix blocks: quarantine + warm-prefill fallback
# --------------------------------------------------------------------------

class TestChecksumQuarantine:
    def test_corrupt_block_quarantined_with_descendants(self, parts, rng):
        prompt = rng.integers(0, 97, (2, 37)).astype(np.int32)
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            with make_engine(parts) as eng:
                eng.prefill(prompt)
                eng.publish(cache)
            n0 = cache.resident_blocks()
            assert n0 >= 4
            # flip one byte of the ROOT block's extent at rest
            chain = cache.match(prompt[0], max_tokens=36)
            root = chain[0]
            cache.store._mm[0, root.start_group, 0, 0, 0, 0] += 1
            cache.pin(chain)
            try:
                with pytest.raises(CorruptBlockError) as ei:
                    cache.read_chain(chain)
            finally:
                cache.unpin(chain)
            assert ei.value.verified_blocks == 0
            # row 0's whole chain hangs off its root ⇒ all of it quarantined;
            # row 1's chain (different prompt) is untouched
            assert cache.resident_blocks() == n0 - len(chain)
            assert cache.stats.corrupt_blocks == 1
            assert cache.stats.quarantined_blocks == len(chain)
            assert cache.match(prompt[0], max_tokens=36) == []
            assert len(cache.match(prompt[1], max_tokens=36)) == len(chain)

    def test_warm_prefill_survives_corruption_bit_identical(self, parts, rng):
        """Acceptance: corrupt a MIDDLE block; warm prefill truncates the
        chain at the last verified block and still produces tokens
        bit-identical to a cold prefill."""
        prompt = rng.integers(0, 97, (2, 37)).astype(np.int32)
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            with make_engine(parts) as cold:
                lc = np.asarray(cold.prefill(prompt))
                cold.publish(cache)
                cold_steps = [np.asarray(cold.decode_step(np.full(2, t)))
                              for t in (5, 9)]
            chain = cache.match(prompt[0], max_tokens=36)
            mid = chain[2]
            cache.store._mm[1, mid.start_group + 1, 0, 1, 0, 3] += 1
            with make_engine(parts) as warm:
                lw = np.asarray(warm.prefill_cached(prompt, cache))
                # blocks 0-1 survive; 2+ quarantined mid-restore
                assert warm.prefill_report["cached_tokens"] == 16
                warm_steps = [np.asarray(warm.decode_step(np.full(2, t)))
                              for t in (5, 9)]
            assert cache.stats.corrupt_blocks == 1
        np.testing.assert_array_equal(lc, lw)
        for a, b in zip(cold_steps, warm_steps):
            np.testing.assert_array_equal(a, b)

    def test_injected_corruption_caught_by_restore(self, parts, rng):
        """End-to-end with the injection hook: corrupt-at-publish blocks are
        never served — warm prefill falls back to cold, bit-identically."""
        prompt = rng.integers(0, 97, (2, 29)).astype(np.int32)
        with PrefixCache(PrefixCacheConfig(block_tokens=8)) as cache:
            cache.use_faults(FaultPlan(FaultSpec(seed=0,
                                                 corrupt_block_rate=1.0)))
            with make_engine(parts) as cold:
                lc = np.asarray(cold.prefill(prompt))
                cold.publish(cache)
            with make_engine(parts) as warm:
                lw = np.asarray(warm.prefill_cached(prompt, cache))
                assert warm.prefill_report["cached_tokens"] == 0
            assert cache.stats.corrupt_blocks >= 1
        np.testing.assert_array_equal(lc, lw)


# --------------------------------------------------------------------------
# prefetch worker survival
# --------------------------------------------------------------------------

class TestWorkerSurvival:
    def test_worker_outlives_failures_and_keeps_serving(self):
        def fetch(layer, n):
            if layer == 1:
                raise TransientReadError("boom", layer=layer)
            return n * 10
        with PrefetchWorker(fetch, n_threads=2) as w:
            bad = [w.submit(1, i) for i in range(4)]
            good = [w.submit(0, i) for i in range(4)]
            for fut in bad:
                with pytest.raises(TransientReadError):
                    fut.result(timeout=5)
            assert [f.result(timeout=5).table for f in good] == [0, 10, 20, 30]
            assert w.alive_threads() == 2
            assert w.deaths == 0

    def test_original_exception_enriched_with_context(self):
        def fetch(layer, *args):
            raise ValueError("boom 5")
        with PrefetchWorker(fetch, n_threads=1) as w:
            fut = w.submit(3, "a", "b")
            with pytest.raises(ValueError, match="boom 5") as ei:
                fut.result(timeout=5)
        assert ei.value.prefetch_layer == 3
        assert ei.value.prefetch_args == ("a", "b")


# --------------------------------------------------------------------------
# session-level robustness
# --------------------------------------------------------------------------

def _run_trace(sess, prompts, max_new=4):
    rids = [sess.submit(p, max_new=max_new, arrival=0.05 * i)
            for i, p in enumerate(prompts)]
    sess.drain()
    return rids

class TestSessionUnderFaults:
    def test_transient_faults_bit_identical_and_no_deaths(self, parts, rng):
        prompts = [rng.integers(0, 97, 24) for _ in range(3)]
        ecfg = make_ecfg(async_io=True)
        with make_session(parts, ecfg=ecfg) as base:
            base_rids = _run_trace(base, prompts)
            ref = {r: base.completed[r].output.tolist() for r in base_rids}
        plan = FaultPlan(FaultSpec(seed=3, read_error_rate=0.25,
                                   torn_read_rate=0.15, error_burst=1))
        with make_session(parts, ecfg=ecfg, faults=plan) as sess:
            rids = _run_trace(sess, prompts)
            stats = sess.stats()
            assert stats["io_retries"] > 0       # campaign was live
            assert stats["failed_requests"] == 0
            assert sess.engine.prefetcher.deaths == 0
            assert sess.engine.prefetcher.alive_threads() == \
                len(sess.engine.prefetcher._threads)
            got = {r: sess.completed[r].output.tolist() for r in rids}
        assert got == {rids[i]: ref[base_rids[i]] for i in range(len(rids))}

    def test_persistent_faults_fail_requests_not_session(self, parts, rng):
        prompts = [rng.integers(0, 97, 24) for _ in range(3)]
        with make_session(parts) as base:
            base_rids = _run_trace(base, prompts)
            ref = [base.completed[r].output.tolist() for r in base_rids]
        plan = FaultPlan(FaultSpec(seed=11, bad_extent_rate=0.35))
        with make_session(parts, faults=plan) as sess:
            rids = _run_trace(sess, prompts)     # must not raise
            stats = sess.stats()
            assert stats["failed_requests"] > 0
            assert stats["failed_requests"] + stats["completed_requests"] \
                == len(rids)
            for i, rid in enumerate(rids):
                if rid in sess.completed:        # survivors are untouched
                    assert sess.completed[rid].output.tolist() == ref[i]
                else:
                    req = sess.failed[rid]
                    assert req.state == FAILED and req.error

    def test_persistent_faults_leave_prefetch_workers_alive(self, parts, rng):
        """Bad extents escalate to failed requests on the async path, never
        to a dead prefetch worker."""
        prompts = [rng.integers(0, 97, 24) for _ in range(3)]
        plan = FaultPlan(FaultSpec(seed=11, bad_extent_rate=0.35))
        with make_session(parts, ecfg=make_ecfg(async_io=True),
                          faults=plan) as sess:
            rids = _run_trace(sess, prompts)
            stats = sess.stats()
            assert stats["failed_requests"] > 0
            assert stats["failed_requests"] + stats["completed_requests"] \
                == len(rids)
            assert sess.engine.prefetcher.deaths == 0
            assert sess.engine.prefetcher.alive_threads() == \
                len(sess.engine.prefetcher._threads)

    def test_decode_fault_fails_culprit_and_replays_survivors(self, parts,
                                                              rng):
        """Force a FetchFailed mid-decode while two rows run: the culprit
        fails, the bystander is replayed and finishes bit-identically."""
        prompts = [rng.integers(0, 97, 20) for _ in range(2)]
        with make_session(parts) as base:
            base_rids = [base.submit(p, max_new=6) for p in prompts]
            base.drain()
            ref = {r: base.completed[r].output.tolist() for r in base_rids}
        with make_session(parts) as sess:
            rids = [sess.submit(p, max_new=6) for p in prompts]
            fired = []
            # patch the disk tier — the retry primitive's home since the
            # tier-chain refactor — so the fault fires inside fetch()
            tier = sess.engine.managers[0].disk
            orig = tier.read_run_with_retry
            def sabotage(bi, run, layer=None):
                if not fired and bi == 1 and sess.engine.row_seq[1] >= 22:
                    fired.append(True)
                    raise FetchFailed("injected mid-decode", layer=0, row=1,
                                      start=run.start, count=run.count)
                return orig(bi, run, layer=layer)
            tier.read_run_with_retry = sabotage
            sess.drain()
            stats = sess.stats()
        assert fired, "sabotage never triggered; adjust the trip condition"
        assert stats["failed_requests"] == 1
        assert stats["recovered_rows"] == 1
        assert sess.failed[rids[1]].state == FAILED
        assert sess.completed[rids[0]].output.tolist() == ref[base_rids[0]]

    def test_admission_fault_fails_only_that_request(self, parts, rng):
        from repro.faults.errors import StorageFault
        prompts = [rng.integers(0, 97, 20) for _ in range(2)]
        with make_session(parts, slots=1) as sess:
            rids = [sess.submit(p, max_new=3) for p in prompts]
            orig = sess.engine.admit_row
            calls = []
            def flaky_admit(bi, tokens, cache=None):
                calls.append(1)
                if len(calls) == 1:
                    raise StorageFault("injected admission failure")
                return orig(bi, tokens, cache)
            sess.engine.admit_row = flaky_admit
            sess.drain()
        assert sess.failed[rids[0]].state == FAILED
        assert sess.completed[rids[1]].state == DONE
        assert len(sess.completed[rids[1]].output) == 3


class TestFrontDoor:
    def test_capacity_rejection_is_typed_and_counted(self, parts):
        with make_session(parts) as sess:
            with pytest.raises(RequestRejected) as ei:
                sess.submit(np.arange(100), max_new=100)
            assert ei.value.reason == "capacity"
            assert sess.stats()["rejected_requests"] == 1

    def test_rejection_never_perturbs_running_rows(self, parts, rng):
        """Satellite acceptance: a mid-flight rejection leaves every running
        request's tokens bit-identical to a run without the rejection."""
        prompts = [rng.integers(0, 97, 20) for _ in range(2)]
        with make_session(parts) as base:
            rids_b = [base.submit(p, max_new=6) for p in prompts]
            base.drain()
            ref = [base.completed[r].output.tolist() for r in rids_b]
        with make_session(parts) as sess:
            rids = [sess.submit(p, max_new=6) for p in prompts]
            sess.step()
            sess.step()
            with pytest.raises(RequestRejected):
                sess.submit(np.arange(100), max_new=100)   # mid-flight
            sess.drain()
            got = [sess.completed[r].output.tolist() for r in rids]
        assert got == ref


class TestDegradationLadder:
    POL = DegradationPolicy(baseline_steps=4, window=3, shed_factor=3.0,
                            recover_factor=1.5)

    def test_sheds_then_recovers(self, parts):
        with make_session(parts, degrade=self.POL) as sess:
            for _ in range(4):
                sess._note_step_latency(0.001)   # healthy baseline
            for _ in range(3):
                sess._note_step_latency(0.010)   # 10x inflation
            assert sess._degrade_level == 1
            with pytest.raises(RequestRejected) as ei:
                sess.submit(np.arange(8), max_new=2)
            assert ei.value.reason == "overload"
            for _ in range(3):
                sess._note_step_latency(0.001)   # storage healthy again
            assert sess._degrade_level == 0
            assert sess.submit(np.arange(8), max_new=2) >= 0

    def test_level2_reduces_group_budget_and_restores(self, parts):
        pol = dataclasses.replace(self.POL, reduce_n_select=True,
                                  min_n_select=2)
        with make_session(parts, degrade=pol) as sess:
            base_n = sess.engine.n_select
            for _ in range(4):
                sess._note_step_latency(0.001)
            for _ in range(6):
                sess._note_step_latency(0.010)
            assert sess._degrade_level == 2
            assert sess.engine.n_select == max(2, base_n // 2)
            for _ in range(6):
                sess._note_step_latency(0.001)
            assert sess._degrade_level == 0
            assert sess.engine.n_select == base_n

    def test_runtime_n_select_is_clamped(self, parts):
        with make_engine(parts) as eng:
            assert eng.set_n_select(1000) == eng.cfg.n_select
            assert eng.set_n_select(0) == 1
            assert eng.set_n_select(eng.cfg.n_select) == eng.cfg.n_select


class TestSpikesInModeledTime:
    def test_gc_stalls_land_in_step_io_seconds(self, parts, rng):
        """Spike seconds must flow into the same io_seconds lane every SLO
        computation reads, plus the dedicated stall counter."""
        prompt = rng.integers(0, 97, (2, 24)).astype(np.int32)
        plan = FaultPlan(FaultSpec(seed=0, spike_rate=1.0,
                                   spike_seconds=0.004))
        with make_engine(parts, disk="emmc") as base:
            base.prefill(prompt)
            for t in (5, 9, 13):
                base.decode_step(np.full(2, t))
            io_base = sum(st.io_seconds for st in base.step_log)
        with make_engine(parts, disk="emmc", faults=plan) as eng:
            lf = np.asarray(eng.prefill(prompt))
            steps = [np.asarray(eng.decode_step(np.full(2, t)))
                     for t in (5, 9, 13)]
            snap = eng.accountant.snapshot()
            io_faulted = sum(st.io_seconds for st in eng.step_log)
        assert snap["stall_seconds"] > 0
        assert io_faulted > io_base          # spikes made modeled I/O slower
        # time-only faults: the numbers the model computes never change
        with make_engine(parts, disk="emmc") as ref:
            lr = np.asarray(ref.prefill(prompt))
            ref_steps = [np.asarray(ref.decode_step(np.full(2, t)))
                         for t in (5, 9, 13)]
        np.testing.assert_array_equal(lf, lr)
        for a, b in zip(steps, ref_steps):
            np.testing.assert_array_equal(a, b)
