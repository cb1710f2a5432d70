"""Disaggregated prefill/decode serving (``repro.disagg``).

The contract under test, end to end:

* a ticket's journey QUEUED → READY → ADMITTED → DONE produces tokens
  **bit-identical** to the same request alone in a co-located session
  (the decode admission restores the published chain at ``kv_bits=16``);
* the lockstep scheduler is deterministic on the modeled clocks — two
  identical runs agree on every latency, not just every token;
* admission sheds typed rejections (capacity, handoff overload) before
  touching any engine;
* the fault ladder stretches across the handoff: a chain corrupted
  between publish and restore is quarantined at the boundary, the ticket
  re-queued for a bounded re-prefill, and **no decode row is ever
  admitted from the quarantined chain** — the request still completes
  bit-identically (or fails terminally once the attempt budget is spent).
"""

import numpy as np
import pytest

from repro.cache import PrefixCache, PrefixCacheConfig
from repro.core.engine import EngineConfig
from repro.disagg import (DONE, FAILED, READY, DisaggFrontEnd, PrefillEngine,
                          PrefillTicket)
from repro.faults import FaultPlan, FaultSpec
from repro.serving.api import ServeSession
from repro.serving.errors import RequestRejected

BLOCK_TOKENS = 8
MAX_NEW = 6


# shadow the session-scoped conftest rng (same convention as test_faults:
# this module must not consume draws from the shared stream)
@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(13)


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


@pytest.fixture(scope="module")
def prompts(tiny_cfg, rng):
    return [rng.integers(0, tiny_cfg.vocab_size, n) for n in (37, 29, 41)]


@pytest.fixture(scope="module")
def solo(parts, prompts):
    """Reference tokens: each request alone in a fresh one-slot session."""
    cfg, params, adapter, calib = parts
    out = []
    for p in prompts:
        with ServeSession(adapter, params, make_ecfg(), slots=1,
                          calib_k=calib) as sess:
            rid = sess.submit(p, MAX_NEW)
            out.append(sess.drain()[rid].output)
    return out


def make_front(parts, cache, *, n_prefill=2, slots=2, **kw):
    cfg, params, adapter, calib = parts
    prefills = [PrefillEngine(f"p{i}", adapter, params, make_ecfg(),
                              cache=cache, calib_k=calib)
                for i in range(n_prefill)]
    decode = ServeSession(adapter, params, make_ecfg(), slots=slots,
                          calib_k=calib, prefix_cache=cache)
    return DisaggFrontEnd(prefills, [decode], cache=cache, **kw)


def restored_floor(n_prompt: int) -> int:
    """Decode admission restores whole published blocks of the prompt's
    first ``n_prompt - 1`` tokens (the last token is always recomputed)."""
    return ((n_prompt - 1) // BLOCK_TOKENS) * BLOCK_TOKENS


def run_front(parts, prompts, **front_kw):
    with PrefixCache(PrefixCacheConfig(block_tokens=BLOCK_TOKENS)) as cache:
        with make_front(parts, cache, **front_kw) as front:
            rids = [front.submit({"prompt": p, "max_new": MAX_NEW,
                                  "arrival": i * 1e-3})
                    for i, p in enumerate(prompts)]
            out = front.drain()
            agg = front.aggregate({})
            return rids, out, agg, front.stats()


class TestHandoffPipeline:
    def test_tokens_bit_identical_to_solo(self, parts, prompts, solo):
        rids, out, agg, stats = run_front(parts, prompts)
        assert stats["completed_requests"] == len(prompts)
        for rid, ref in zip(rids, solo):
            np.testing.assert_array_equal(out[rid], ref)
        # the handoff actually exercised the publish → restore boundary
        assert stats["prefill_published_blocks"] > 0
        assert stats["prefix_hit_rate"] > 0
        assert stats["requeues"] == 0 and stats["ticket_failures"] == 0

    def test_fault_free_run_sheds_and_fails_nothing(self, parts, prompts):
        """With no fault planted and room at the boundary, no submission is
        shed and no request fails."""
        rids, _, agg, stats = run_front(parts, prompts)
        assert stats["handoff_rejections"] == 0
        assert stats["failed_requests"] == 0
        assert len(agg["per_request"]) == len(rids)

    def test_restored_tokens_surfaced_per_request(self, parts, prompts):
        """Satellite: the decode admission's restore depth is visible in
        per-request stats, and equals exactly the prompt's published whole
        blocks — proving every admission came off the prefill pool's
        chain, not a cold prefill."""
        rids, _, agg, _ = run_front(parts, prompts)
        by_rid = {rec["rid"]: rec for rec in agg["per_request"]}
        assert sorted(by_rid) == sorted(rids)
        for rid, p in zip(rids, prompts):
            rec = by_rid[rid]
            assert rec["restored_tokens"] == restored_floor(len(p))
            assert rec["prefill_attempts"] == 1
            assert rec["prefill_engine"] and rec["decode"]

    def test_lockstep_is_deterministic(self, parts, prompts):
        """Two identical runs agree on every modeled latency, not just
        every token — the laggard-first scheduler has no hidden state."""
        _, out1, agg1, _ = run_front(parts, prompts)
        _, out2, agg2, _ = run_front(parts, prompts)
        for rid in out1:
            np.testing.assert_array_equal(out1[rid], out2[rid])
        for r1, r2 in zip(agg1["per_request"], agg2["per_request"]):
            for k in ("ttft_seconds", "tpot_seconds", "e2e_seconds"):
                assert r1[k] == r2[k], (r1["rid"], k)

    def test_ticket_lifecycle_lands_done(self, parts, prompts):
        with PrefixCache(PrefixCacheConfig(block_tokens=BLOCK_TOKENS)) as c:
            with make_front(parts, c) as front:
                rid = front.submit({"prompt": prompts[0],
                                    "max_new": MAX_NEW})
                front.drain()
                front.result(rid)       # marks DONE on read
                t = front.tickets[rid]
                assert t.state == DONE
                assert t.chain_head is not None
                assert t.ready_time is not None and t.decode_rid is not None

    def test_capacity_rejection_precedes_engines(self, parts, rng):
        with PrefixCache(PrefixCacheConfig(block_tokens=BLOCK_TOKENS)) as c:
            with make_front(parts, c) as front:
                huge = rng.integers(0, 97, 4096)
                with pytest.raises(RequestRejected) as ei:
                    front.submit({"prompt": huge, "max_new": MAX_NEW})
                assert ei.value.reason == "capacity"
                assert not front.tickets
                assert all(not pe.has_work for pe in front.prefills)

    def test_handoff_overload_sheds(self, parts, prompts):
        with PrefixCache(PrefixCacheConfig(block_tokens=BLOCK_TOKENS)) as c:
            with make_front(parts, c, max_handoff_depth=1) as front:
                # a READY ticket parked at the boundary fills the queue
                parked = PrefillTicket(rid=999, prompt=prompts[0],
                                       max_new=1)
                parked.state = READY
                front.handoff.append(parked)
                with pytest.raises(RequestRejected) as ei:
                    front.submit({"prompt": prompts[1],
                                  "max_new": MAX_NEW})
                assert ei.value.reason == "handoff_overload"
                assert front.handoff_rejections == 1
                front.handoff.clear()


class TestCorruptHandoff:
    """Satellite: seeded at-rest corruption between publish and restore."""

    def test_corrupt_chain_requeues_then_completes_bit_identical(
            self, parts, prompts, solo):
        prompt, ref = prompts[0], solo[0]
        with PrefixCache(PrefixCacheConfig(block_tokens=BLOCK_TOKENS)) as c:
            # every published block is corrupted at rest the moment it is
            # written (rate=1.0) — the handoff verifier must catch it
            c.use_faults(FaultPlan(FaultSpec(seed=0,
                                             corrupt_block_rate=1.0)))
            with make_front(parts, c, n_prefill=1, slots=1) as front:
                rid = front.submit({"prompt": prompt, "max_new": MAX_NEW})
                while front.requeues == 0 and front.has_work:
                    front.step()
                ticket = front.tickets[rid]
                # the corrupt chain was quarantined at the boundary and the
                # ticket bounced back to prefill — no decode row was ever
                # admitted from it
                assert front.requeues == 1 and ticket.attempts == 1
                assert ticket.decode_rid is None
                assert c.stats.corrupt_blocks >= 1
                assert c.stats.quarantined_blocks >= 1
                assert front.decodes[0].active_rows == 0
                assert front.decodes[0].queue_depth == 0
                # detach the plan before the re-prefill: the corrupt draw
                # is keyed on block_id alone, so a still-attached plan
                # would deterministically re-corrupt the re-published
                # chain forever
                c.use_faults(None)
                out = front.drain()
                np.testing.assert_array_equal(out[rid], ref)
                assert ticket.state == DONE and ticket.attempts == 2
                assert front.ticket_failures == 0
                rec = front.aggregate({})["per_request"][0]
                assert rec["prefill_attempts"] == 2
                # the decode admission restored the *clean* re-published
                # chain, full blocks and all
                assert rec["restored_tokens"] == restored_floor(len(prompt))

    def test_persistent_corruption_fails_terminally(self, parts, prompts):
        """The re-prefill ladder is bounded: corruption that survives every
        attempt fails the ticket, it never loops and never reaches
        decode."""
        with PrefixCache(PrefixCacheConfig(block_tokens=BLOCK_TOKENS)) as c:
            c.use_faults(FaultPlan(FaultSpec(seed=0,
                                             corrupt_block_rate=1.0)))
            with make_front(parts, c, n_prefill=1, slots=1,
                            max_prefill_attempts=2) as front:
                rid = front.submit({"prompt": prompts[0],
                                    "max_new": MAX_NEW})
                out = front.drain()     # terminates despite the bad plan
                assert out == {}
                ticket = front.tickets[rid]
                assert ticket.state == FAILED
                assert ticket.attempts == 2
                assert "corrupt" in ticket.error
                assert front.requeues == 1
                assert front.ticket_failures == 1
                assert ticket.decode_rid is None
                assert front.decodes[0].stats()["completed_requests"] == 0
