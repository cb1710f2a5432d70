"""ServeSession's sampling: the step's greedy rows read from one batched
device argmax, every other row from its own sampler, against a reference
loop that samples each row from a host copy of the step's logits."""

import numpy as np
import pytest

from repro.core.engine import EngineConfig
from repro.serving.api import ServeSession
from repro.serving.sampling import SamplingParams


def make_cfg():
    return EngineConfig(group_size=4, n_select=6, rank=8, reuse_capacity=12, max_seq=128)


@pytest.fixture(scope="module")
def setup(tiny_cfg, tiny_params, tiny_adapter):
    # a generator of its own: the session-wide ``rng`` is left as it was
    calib = np.random.default_rng(17).standard_normal(
        (256, tiny_cfg.n_kv_heads, tiny_cfg.head_dim)).astype(np.float32)
    return tiny_cfg, tiny_params, tiny_adapter, calib


class HostSampledSession(ServeSession):
    """The reference loop: each decode step's logits block copied to the
    host whole, and every row sampled by its own sampler from its host
    row, one call a row."""

    def step(self):
        events = []
        if not self._active() and self._waiting:
            self.now = max(self.now, min(r.arrival for r in self._waiting))
        self._admit_due(events)
        if not self._active():
            return events
        toks = np.zeros(self.n_slots, dtype=np.int64)
        for i in self._active():
            slot = self._slots[i]
            logits = np.asarray(slot.logits)
            tok = int(np.asarray(slot.sampler(logits)).reshape(-1)[0])
            slot.out.append(tok)
            if len(slot.out) == 1:
                slot.req.first_token_at = self.now
            events.append({"type": "token", "rid": slot.req.rid, "slot": i,
                           "token": tok})
            if tok in slot.stop_set:
                slot.req.stopped_early = True
                self._finish(i, events)
            elif len(slot.out) >= slot.req.max_new:
                self._finish(i, events)
            else:
                toks[i] = tok
        active = self._active()
        if active:
            logits = np.asarray(self.engine.decode_step(toks))
            self.now += self.engine.step_log[-1].pipelined_seconds
            for i in active:
                self._slots[i].logits = logits[i:i + 1]
        return events


def second_best(logits):
    """A raw ``sampler=`` override: the runner-up of each row."""
    return np.argsort(np.asarray(logits, np.float32), axis=-1)[:, -2]


STOCHASTIC = [SamplingParams(temperature=0.8, top_k=8, seed=7),
              SamplingParams(temperature=1.2, top_p=0.9, seed=3),
              SamplingParams(temperature=0.7, top_k=5, top_p=0.8, seed=11)]

# (scheduler step at which it is submitted, prompt length, max_new, kwargs)
SAMPLING_CASES = {
    "greedy": [(0, 23, 6, {}), (0, 17, 4, {}), (0, 30, 5, {})],
    "stochastic": [(0, 21, 6, {"sampling": STOCHASTIC[0]}),
                   (0, 19, 5, {"sampling": STOCHASTIC[1]}),
                   (0, 26, 5, {"sampling": STOCHASTIC[2]})],
    "mixed": [(0, 22, 6, {}), (0, 18, 6, {"sampling": STOCHASTIC[0]}),
              (0, 25, 5, {"sampler": second_best}),
              (0, 20, 4, {"sampling": STOCHASTIC[1]})],
    # the first request stops on a token it emits mid-stream; the second
    # retires at its budget while the others decode; the rest arrive later
    "staggered_stop_retire": [(0, 24, 8, {"stop": True}), (0, 16, 3, {}),
                              (2, 27, 5, {}), (4, 19, 4, {})],
}


def _serve(cls, setup, case, stop_ids=()):
    cfg, params, adapter, calib = setup
    prompts = np.random.default_rng(18).integers(0, cfg.vocab_size, 32)
    pending = sorted(SAMPLING_CASES[case], key=lambda r: r[0])
    events, rids, step = [], [], 0
    with cls(adapter, params, make_cfg(), slots=3, calib_k=calib) as sess:
        while pending or sess.has_work:
            while pending and pending[0][0] <= step:
                _, n, max_new, kw = pending.pop(0)
                kw = dict(kw)
                stops = stop_ids if kw.pop("stop", False) else ()
                rids.append(sess.submit(prompts[:n], max_new, stop_ids=stops, **kw))
            events.extend(sess.step())
            step += 1
        done = sess.drain()
        outs = [done[r].output for r in rids]
        stopped = [done[r].stopped_early for r in rids]
    return events, outs, stopped


@pytest.mark.parametrize("case", sorted(SAMPLING_CASES))
def test_device_sampling_matches_host_sampling(setup, case):
    """Sampling the step's logits on the device (one batched argmax for
    greedy rows, device rows for the rest) serves the token streams and
    events that sampling each row from a host copy serves."""
    stop_ids = ()
    if "stop" in str(SAMPLING_CASES[case]):
        _, outs, _ = _serve(HostSampledSession, setup, case)
        first = outs[0]
        stop_ids = (next(int(t) for k, t in enumerate(first)
                         if 2 <= k < len(first) - 1 and t not in first[:k]),)
    ref = _serve(HostSampledSession, setup, case, stop_ids)
    got = _serve(ServeSession, setup, case, stop_ids)
    assert got[0] == ref[0]
    for a, b in zip(got[1], ref[1]):
        np.testing.assert_array_equal(a, b)
    assert got[2] == ref[2]
    if stop_ids:
        assert got[2][0] and len(got[1][0]) < SAMPLING_CASES[case][0][2]


def test_greedy_rows_take_the_batched_path(setup):
    """Steady all-greedy decoding samples every active row from the one
    batched argmax; a raw override still gets a host ``[1, V]`` row."""
    from repro.obs import Observability

    cfg, params, adapter, calib = setup
    prompts = np.random.default_rng(19).integers(0, cfg.vocab_size, (3, 20))
    obs = Observability()
    counter = obs.registry.counter
    batched = counter("kvswap_sample_batched_rows_total")
    calls = counter("kvswap_sample_row_calls_total")
    with ServeSession(adapter, params, make_cfg(), slots=2, calib_k=calib,
                      obs=obs) as sess:
        for p in prompts[:2]:
            sess.submit(p, 6)
        sess.step()            # first tokens come from the admissions' logits
        assert (batched.value, calls.value) == (0, 2)
        for n in range(1, 5):
            sess.step()
            assert (batched.value, calls.value) == (2 * n, 2)
        sess.drain()
        seen = []

        def recorded(logits):
            seen.append((type(logits), logits.shape))
            return second_best(logits)

        rid = sess.submit(prompts[2], 4, sampler=recorded)
        sess.drain()
        assert len(sess.completed[rid].output) == 4
    assert seen == [(np.ndarray, (1, cfg.vocab_size))] * 4
    assert calls.value == 2 + 4
