"""Device-resident decode hot path: bit-identity, delta uploads, fused
prediction, jitted sampling, metadata accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EngineConfig, KVSwapEngine
from repro.core.predictor import (fused_predict, group_scores, lowrank_queries,
                                  select_groups, token_scores)
from repro.core.reuse_buffer import ReuseBuffer, _pad_bucket
from repro.models.transformer import ModelConfig, TransformerAdapter, init_params


def make_engine(adapter, params, calib, *, batch=2, **kw):
    base = dict(group_size=4, n_select=6, rank=8, reuse_capacity=12, max_seq=128)
    base.update(kw)
    return KVSwapEngine(adapter, params, EngineConfig(**base), batch=batch,
                        calib_k=calib)


@pytest.fixture(scope="module")
def setup(tiny_cfg, tiny_params, tiny_adapter, rng):
    prompt = rng.integers(0, tiny_cfg.vocab_size, (2, 37)).astype(np.int32)
    calib = rng.standard_normal(
        (256, tiny_cfg.n_kv_heads, tiny_cfg.head_dim)).astype(np.float32)
    return tiny_cfg, tiny_params, tiny_adapter, prompt, calib


class TestBitIdentity:
    """The hard contract: device-resident and host-gather decode the same
    tokens, bit for bit, across every config axis."""

    @pytest.mark.slow  # superseded in default CI by tests/test_equality_matrix.py
    @pytest.mark.parametrize("predict_from", ["prev", "self"])
    @pytest.mark.parametrize("kv_bits", [16, 8])
    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_greedy_decode_matches_host_gather(self, setup, predict_from,
                                               kv_bits, use_pallas):
        cfg, params, adapter, prompt, calib = setup
        outs = {}
        for dr in (False, True):
            with make_engine(adapter, params, calib, predict_from=predict_from,
                             kv_bits=kv_bits, use_pallas=use_pallas,
                             device_resident=dr) as eng:
                outs[dr] = eng.generate(prompt, 6)
                assert eng.device_resident is dr
        np.testing.assert_array_equal(outs[False], outs[True])

    @pytest.mark.slow  # superseded in default CI by tests/test_equality_matrix.py
    def test_identity_through_async_pipeline(self, setup):
        cfg, params, adapter, prompt, calib = setup
        outs = {}
        for dr in (False, True):
            with make_engine(adapter, params, calib, async_io=True,
                             device_resident=dr) as eng:
                outs[dr] = eng.generate(prompt, 8)
        np.testing.assert_array_equal(outs[False], outs[True])

    def test_identity_with_staged_overflow(self, setup):
        """C smaller than the working set forces the staged (-2) path: the
        device gather's transient-override rows must match host staging."""
        cfg, params, adapter, prompt, calib = setup
        outs = {}
        for dr in (False, True):
            with make_engine(adapter, params, calib, reuse_capacity=4,
                             device_resident=dr) as eng:
                outs[dr] = eng.generate(prompt, 8)
        np.testing.assert_array_equal(outs[False], outs[True])

    def test_device_matches_full_kv_oracle_under_full_coverage(self, setup):
        """Transitivity check against the model itself, not just the control
        path: full-rank adapter + M covering all groups ⇒ exact decode."""
        from tests.test_engine import full_kv_reference_generate

        cfg, params, adapter, prompt, _ = setup
        feat = cfg.n_kv_heads * cfg.head_dim
        calib = np.random.default_rng(1).standard_normal(
            (256, cfg.n_kv_heads, cfg.head_dim))
        with make_engine(adapter, params, calib, n_select=64, rank=feat,
                         reuse_capacity=64, predict_from="self",
                         device_resident=True) as eng:
            got = eng.generate(prompt, 8)
        want = full_kv_reference_generate(params, cfg, prompt, 8)
        np.testing.assert_array_equal(got, want)


class TestDeltaUploads:
    def test_reuse_hit_step_uploads_zero_group_bytes(self, setup):
        """Fig. 8's payoff: once the working set is resident, a decode step
        moves no group bytes host→device.  Asserted through a transfer-
        counting shim wrapped around the manager's sync_device."""
        cfg, params, adapter, prompt, calib = setup
        # G=8, prompt 37 ⇒ rolling fill starts at 5: the first 3 steps see a
        # fixed on-disk group set (no flush ⇒ no new groups); M covers every
        # prompt group and C holds them all ⇒ steps 2-3 are pure hits
        with make_engine(adapter, params, calib, group_size=8, n_select=8,
                         reuse_capacity=16, device_resident=True) as eng:
            logits = eng.prefill(prompt)
            upload_log = []
            for j, mgr in enumerate(eng.managers):
                orig = mgr.sync_device
                mgr.sync_device = (lambda table, _o=orig:
                                   upload_log.append(_o(table)) or upload_log[-1])
            for _ in range(3):
                tok = np.asarray(jnp.argmax(logits, axis=-1))
                upload_log.clear()
                logits = eng.decode_step(tok)
                step_bytes = sum(upload_log)
                assert eng.step_log[-1].h2d_bytes == step_bytes
            # the last step's working set was fully resident
            assert step_bytes == 0
            assert eng.step_log[-1].h2d_bytes == 0
            # the engine-level counters agree with the mirror's own
            mirrors = [r.device for r in eng.reuse]
            assert all(m is not None for m in mirrors)
            total_mirror = sum(m.uploaded_bytes for m in mirrors)
            total_steps = sum(s.h2d_bytes for s in eng.step_log)
            assert total_mirror == total_steps

    def test_first_step_uploads_then_hits(self, setup):
        cfg, params, adapter, prompt, calib = setup
        with make_engine(adapter, params, calib, group_size=8, n_select=8,
                         reuse_capacity=16, device_resident=True) as eng:
            logits = eng.prefill(prompt)
            for _ in range(3):
                logits = eng.decode_step(
                    np.asarray(jnp.argmax(logits, axis=-1)))
            log = [s.h2d_bytes for s in eng.step_log]
            assert log[0] > 0          # cold fetch ships the working set
            assert log[-1] == 0        # steady state ships nothing

    def test_host_gather_path_reports_full_reupload(self, setup):
        cfg, params, adapter, prompt, calib = setup
        with make_engine(adapter, params, calib,
                         device_resident=False) as eng:
            eng.generate(prompt, 3)
            # every step re-uploads the assembled context for every layer
            assert all(s.h2d_bytes > 0 for s in eng.step_log)


class TestDeviceMirror:
    def test_scatter_matches_host_slots(self, rng):
        buf = ReuseBuffer(batch=2, capacity=4, group_size=4, n_kv_heads=2,
                          head_dim=8)
        mirror = buf.attach_device_mirror()
        entries = []
        for bi in range(2):
            for gid in range(3):
                kv = rng.standard_normal((4, 2, 2, 8)).astype(np.float32)
                slot = buf.insert(bi, gid, kv)
                entries.append((bi, slot, kv))
        assert mirror.scatter(entries) > 0
        np.testing.assert_array_equal(
            np.asarray(mirror.k), buf.slots[:, :, :, 0])
        np.testing.assert_array_equal(
            np.asarray(mirror.v), buf.slots[:, :, :, 1])

    def test_empty_scatter_is_free(self):
        buf = ReuseBuffer(batch=1, capacity=2, group_size=4, n_kv_heads=2,
                          head_dim=8)
        mirror = buf.attach_device_mirror()
        assert mirror.scatter([]) == 0
        assert mirror.uploaded_bytes == 0
        assert mirror.scatter_calls == 0

    def test_pad_bucket_sizes(self):
        assert [_pad_bucket(n) for n in (0, 1, 7, 8, 9, 16, 17, 63)] == \
            [8, 8, 8, 8, 16, 16, 32, 64]


class TestFusedPredictor:
    def test_matches_op_by_op_pipeline(self, rng):
        from repro.core.lowrank import fit_adapter

        calib = rng.standard_normal((128, 2, 16)).astype(np.float32)
        adapter = fit_adapter(calib, rank=8)
        q = jnp.asarray(rng.standard_normal((2, 4, 16)).astype(np.float32))
        k_lr = jnp.asarray(rng.standard_normal((2, 64, 8)).astype(np.float32))
        ids, mask = fused_predict(q, adapter.per_head, k_lr, jnp.int32(60),
                                  group_size=4, n_select=6)
        q_lr = lowrank_queries(q, adapter, 4)
        gs = group_scores(token_scores(q_lr, k_lr), 4, jnp.int32(60))
        ids_ref, mask_ref = select_groups(gs, 6)
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_ref))
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_ref))

    def test_pallas_variant_selects_same_groups(self, rng):
        from repro.core.lowrank import fit_adapter
        from repro.kernels import fused_predict_pallas

        calib = rng.standard_normal((128, 2, 16)).astype(np.float32)
        adapter = fit_adapter(calib, rank=8)
        q = jnp.asarray(rng.standard_normal((2, 4, 16)).astype(np.float32))
        k_lr = jnp.asarray(rng.standard_normal((2, 64, 8)).astype(np.float32))
        ids, mask = fused_predict(q, adapter.per_head, k_lr, jnp.int32(60),
                                  group_size=4, n_select=6)
        ids_p, mask_p = fused_predict_pallas(
            q, adapter.per_head, k_lr, jnp.full((2,), 60, jnp.int32),
            group_size=4, n_select=6)
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_p))
        np.testing.assert_array_equal(np.asarray(ids), np.asarray(ids_p))


class TestSatellites:
    def test_metadata_counts_kv_layers_only(self, rng):
        """k_lr_logical must scale with KV layers (hybrid: not all layers)."""
        cfg = ModelConfig(name="hyb", arch_type="hybrid", n_layers=3,
                          d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
                          d_ff=128, vocab_size=61,
                          block_pattern=("mamba2", "shared_attn", "mamba2"),
                          ssm_state=16)
        params = init_params(jax.random.PRNGKey(1), cfg)
        adapter = TransformerAdapter(cfg)
        calib = rng.standard_normal((128, 4, 16)).astype(np.float32)
        prompt = rng.integers(0, 61, (2, 20)).astype(np.int32)
        with make_engine(adapter, params, calib, n_select=8, rank=16,
                         reuse_capacity=8, max_seq=64) as eng:
            eng.prefill(prompt)
            m = eng.metadata_bytes()
            # 1 KV layer of 3: per-layer valid-token footprint, counted once
            assert m["k_lr_logical"] == 2 * eng.valid_tokens * 16 * 4 * 1
            assert m["total"] == m["k_lr_alloc"] + m["reuse_buffer"] + m["rolling_buffer"]

    def test_metadata_reports_device_mirror(self, setup):
        cfg, params, adapter, prompt, calib = setup
        with make_engine(adapter, params, calib, device_resident=True) as eng:
            logits = eng.prefill(prompt)
            eng.decode_step(np.asarray(jnp.argmax(logits, axis=-1)))
            m = eng.metadata_bytes()
            assert m["device_mirror"] == sum(r.device.nbytes for r in eng.reuse)

    def test_generate_nongreedy_vectorized(self, setup):
        """The non-greedy branch draws one vectorized categorical per step
        (serving sampler), deterministic under a seeded rng."""
        cfg, params, adapter, prompt, calib = setup
        outs = []
        for _ in range(2):
            with make_engine(adapter, params, calib) as eng:
                outs.append(eng.generate(prompt, 5, greedy=False,
                                         rng=np.random.default_rng(7)))
        assert outs[0].shape == (2, 5)
        assert (outs[0] >= 0).all() and (outs[0] < cfg.vocab_size).all()
        np.testing.assert_array_equal(outs[0], outs[1])

    def test_generate_returns_host_array(self, setup):
        cfg, params, adapter, prompt, calib = setup
        with make_engine(adapter, params, calib) as eng:
            out = eng.generate(prompt, 3)
        assert isinstance(out, np.ndarray)
        assert out.shape == (2, 3)

    def test_rolling_advance_counts_like_append(self):
        from repro.core.rolling_buffer import RollingBuffer

        rb = RollingBuffer(batch=2, group_size=4, n_kv_heads=2, head_dim=8)
        assert [rb.advance() for _ in range(4)] == [False, False, False, True]
        assert rb.fill == 0


def _greedy_staggered(eng, prompts, admit_at, n_steps):
    """Admit ``prompts[bi]`` into row ``bi`` before step ``admit_at[bi]`` and
    decode greedily; returns the ``[n_steps, B]`` tokens fed back."""
    tok = np.zeros(eng.batch, np.int64)
    out = []
    for step in range(n_steps):
        for bi, at in enumerate(admit_at):
            if at == step:
                tok[bi] = int(np.argmax(np.asarray(eng.admit_row(bi, prompts[bi]))))
        logits = np.asarray(eng.decode_step(tok))
        tok = np.where(eng.row_active, logits.argmax(-1), tok)
        out.append(tok.copy())
    return np.stack(out)


def _tiny_model(n_layers):
    cfg = ModelConfig(name="tiny", arch_type="dense", n_layers=n_layers,
                      d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, vocab_size=97)
    return TransformerAdapter(cfg), init_params(jax.random.PRNGKey(0), cfg)


def _assert_klr_compresses_store(eng):
    """Each row's ``k_lr`` holds its stored keys, compressed, up to its
    watermark, and nothing past it."""
    from repro.core.lowrank import compress_k

    for j, k_lr in enumerate(eng.k_lr):
        k_lr = np.asarray(k_lr)
        for bi in range(eng.batch):
            n = int(eng.row_valid[bi])
            assert n == eng.store.n_groups[j, bi] * eng.cfg.group_size
            k = eng.store._mm[j, bi, : n // eng.cfg.group_size, :, 0]
            want = compress_k(jnp.asarray(k.reshape(1, n, *k.shape[2:])), eng.adapter)
            np.testing.assert_array_equal(k_lr[bi, :n], np.asarray(want)[0])
            np.testing.assert_array_equal(k_lr[bi, n:], 0)


class TestStepEndFlush:
    """Every group a decode step completes is flushed at the step's end by
    one program and one download, whatever rows and layers complete."""

    @pytest.mark.parametrize("async_io", [False, True])
    @pytest.mark.parametrize("kv_bits", [16, 8])
    def test_staggered_rows_match_host_gather(self, setup, async_io, kv_bits):
        """Rows admitted at offsets that complete groups in different steps:
        tokens, ``k_lr`` and the store's bytes equal the host-gather
        control's."""
        cfg, params, adapter, _, calib = setup
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, cfg.vocab_size, n) for n in (37, 30, 24)]
        got = {}
        for dr in (False, True):
            with make_engine(adapter, params, calib, batch=3, async_io=async_io,
                             kv_bits=kv_bits, device_resident=dr) as eng:
                toks = _greedy_staggered(eng, prompts, (0, 2, 3), 12)
                flushed = [s.flushed_groups for s in eng.step_log]
                if kv_bits == 16:
                    _assert_klr_compresses_store(eng)
                got[dr] = (toks, [np.asarray(k) for k in eng.k_lr],
                           np.array(eng.store._mm), eng.store.n_groups.copy(),
                           None if kv_bits == 16 else eng.store._scales.copy())
        n_kv = 2
        # the schedule completes groups of some rows only, in several steps
        assert any(0 < f < 3 * n_kv for f in flushed)
        assert sum(f > 0 for f in flushed) >= 4
        for a, b in zip(got[False], got[True]):
            if isinstance(a, list):
                for x, y in zip(a, b):
                    np.testing.assert_array_equal(x, y)
            elif a is not None:
                np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("batch,n_layers", [(1, 1), (3, 2), (4, 3)])
    def test_flush_step_makes_one_dispatch_and_one_download(
            self, monkeypatch, batch, n_layers):
        from repro.core import engine as engine_mod

        model, params = _tiny_model(n_layers)
        rng = np.random.default_rng(2)
        calib = rng.standard_normal((256, 2, 16)).astype(np.float32)
        prompt = rng.integers(0, 97, (batch, 37)).astype(np.int32)
        calls = {"flush": 0, "get": 0, "append_row": 0}

        def counted(fn, key):
            def wrapper(*args, **kwargs):
                calls[key] += 1
                return fn(*args, **kwargs)
            return wrapper

        with make_engine(model, params, calib, batch=batch) as eng:
            logits = eng.prefill(prompt)
            monkeypatch.setattr(engine_mod, "_flush_groups",
                                counted(engine_mod._flush_groups, "flush"))
            monkeypatch.setattr(engine_mod, "_klr_append_row",
                                counted(engine_mod._klr_append_row, "append_row"))
            monkeypatch.setattr(jax, "device_get", counted(jax.device_get, "get"))
            per_step = []
            for _ in range(5):
                calls.update(flush=0, get=0, append_row=0)
                logits = eng.decode_step(np.asarray(jnp.argmax(logits, axis=-1)))
                per_step.append(dict(calls))
            flushed = [s.flushed_groups for s in eng.step_log]
        # a tail of 37 % 4 = 1 token: the third step completes every row;
        # each step's other downloads are the layers' selections
        for step, c in enumerate(per_step):
            flush = step == 2
            assert c == {"flush": int(flush), "get": n_layers + flush,
                         "append_row": 0}, (step, c)
        assert flushed == [0, 0, batch * n_layers, 0, 0]

    @pytest.mark.parametrize("device_resident", [False, True])
    def test_flushed_groups_counts_rows_times_kv_layers(self, setup,
                                                        device_resident):
        from repro.core.engine import summarize_steps

        cfg, params, adapter, prompt, calib = setup
        with make_engine(adapter, params, calib,
                         device_resident=device_resident) as eng:
            eng.generate(prompt, 9)
            flushed = [s.flushed_groups for s in eng.step_log]
            n_kv = len(eng.kv_layers)
            # prompt 37: the rows complete a group on steps 3 and 7
            assert flushed == [0, 0, 2 * n_kv, 0, 0, 0, 2 * n_kv, 0, 0]
            assert summarize_steps(eng.step_log)["flushed_groups"] == 4 * n_kv
            assert eng.store.n_groups.tolist() == [[11, 11]] * n_kv
