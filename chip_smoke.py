#!/usr/bin/env python3
"""Run the served KVSwap path once on one TPU, at Qwen3-8B widths.

    python chip_smoke.py          # needs a TPU; exits nonzero without one
    python chip_smoke.py --tiny   # the same phases at qwen3_8b.smoke_config(),
                                  # on whatever device JAX finds (CPU rehearsal)

The model is ``repro.configs.qwen3_8b.config()`` at its published widths
(d_model 4096, 32 query / 8 KV heads of 128, d_ff 12288, vocab 151936,
qk-norm, untied head), with its depth cut to 4 of 36 layers and random
float32 weights from ``--seed``.  Token ids are drawn from the same seed.

Phases, each of which exits nonzero when its check fails:

(a) reference — ``KVSwapEngine`` with ``n_select`` and ``reuse_capacity`` so
    large that every KV group is selected and resident, so selected attention
    equals full attention.  Prefill plus teacher-forced decode steps; the
    last-position logits of each are compared with ``transformer.forward``
    on the same tokens under ``jax.default_matmul_precision("highest")``.
(b) served — ``ServeSession`` with the engine's default selection
    (``n_select=100``, ``G=4``), two slots, five requests of 512–2048 prompt
    tokens submitted while earlier ones decode, two of them sharing a
    1024-token prefix through a ``PrefixCache``.  Drained; every request must
    finish with finite logits, and the disk tier must have served reads.
(c) pallas — the engine of (a) with ``use_pallas=True`` on the same tokens.
    On a TPU the Pallas kernels must be in the lowered program as Mosaic
    custom calls (compiled, not interpreted); its logits are compared with
    (a)'s and with the reference, and the scoring kernel with its jnp oracle.

Times and memory are printed as "measured on <device kind>"; anything from
``DiskSpec``/``ComputeSpec`` is printed as "modeled".  The last line of
standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.cache import PrefixCache, PrefixCacheConfig  # noqa: E402
from repro.configs import qwen3_8b  # noqa: E402
from repro.core.engine import EngineConfig, KVSwapEngine  # noqa: E402
from repro.core.lowrank import fit_adapter  # noqa: E402
from repro.kernels import fused_predict_pallas, ops, ref  # noqa: E402
from repro.models.transformer import TransformerAdapter, forward, init_params  # noqa: E402
from repro.serving.api import ServeSession  # noqa: E402

# Sizes per mode.  Full: prompts stay <= 2048 tokens so one row's eager
# prefill scores ([1, 32, S, S] f32) stay near 0.5 GB next to ~8.1 GB of
# weights.  Tiny: the same schedule scaled to the smoke config, with a
# selection budget small enough that selection is active there too.
SIZES = {
    "full": dict(layers=4, ref_batch=2, ref_prompt=1024, ref_steps=8,
                 served_prompts=(1536, 512, 2048, 1536, 1024), shared=1024,
                 submit_at=(0, 4, 8, 16, 24), max_new=32, n_select=100,
                 reuse_capacity=160, calib_tokens=256),
    "tiny": dict(layers=None, ref_batch=2, ref_prompt=64, ref_steps=8,
                 served_prompts=(192, 64, 256, 192, 128), shared=128,
                 submit_at=(0, 1, 2, 4, 6), max_new=8, n_select=16,
                 reuse_capacity=24, calib_tokens=64),
}

# Reference tolerance.  The engine's float32 matmuls run at the backend's
# default precision: on a TPU that is one bfloat16 pass per matmul (inputs
# rounded to 8 significant bits), on the CPU full float32.  So the bound is
# set by what the plain forward itself shows at that precision: the served
# path may differ from the "highest" reference by at most REF_FACTOR times
# the error of ``transformer.forward`` at default precision (prefill is
# eager and decode jitted, so summation order and fusion differ from the
# forward's), and never needs to beat a float32 floor of REF_FLOOR relative
# to the largest reference logit.
REF_FACTOR = 4.0
REF_FLOOR = 1e-4
# Group scores of the Pallas scoring kernel against its jnp oracle (highest
# precision), relative to the largest score: the kernel's float32 matmul may
# run as one bfloat16 pass, 2^-8 relative per product.
SCORE_TOL = 1e-2


class _CheckedGreedy:
    """Greedy sampler that records whether every logits row it saw was
    finite (the served path's per-request output check)."""

    def __init__(self):
        self.rows = 0
        self.finite = True

    def __call__(self, logits):
        lg = np.asarray(logits)
        self.rows += 1
        self.finite &= bool(np.isfinite(lg).all())
        return lg.argmax(axis=-1)


class _CompileCounter:
    """Counts programs built for the backend and how many of them came from
    the persistent cache, from JAX's monitoring events (the compile-duration
    event fires for a cache load too)."""

    def __init__(self):
        self.compiles = 0
        self.compile_seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_seconds, self.cache_hits

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


class Phase:
    """Times one phase on the host clock (callers end their work with
    ``block_until_ready`` or a host pull) and reports compiles and peak
    device memory, labelled with the device it was measured on."""

    def __init__(self, name, dev, counter):
        self.name, self.dev, self.counter = name, dev, counter

    def __enter__(self):
        self.c0 = self.counter.snapshot()
        self.t0 = time.perf_counter()
        print(f"[{self.name}] start", flush=True)
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        wall = time.perf_counter() - self.t0
        c1 = self.counter.snapshot()
        stats = self.dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"[{self.name}] measured on {self.dev.device_kind}: "
              f"wall {wall:.3f} s (compiles included), "
              f"{c1[0] - self.c0[0]} programs built "
              f"({c1[1] - self.c0[1]:.3f} s), "
              f"{c1[2] - self.c0[2]} of them persistent-cache hits, "
              f"peak device bytes {peak if peak is not None else 'not reported'}",
              flush=True)
        return False


def _err(got, want):
    """Max abs error, and that error relative to the largest |reference|."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = float(np.abs(got - want).max())
    return err, err / float(np.abs(want).max())


def _check(name, ok, msg):
    print(f"[{name}] {'PASS' if ok else 'FAIL'}: {msg}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: phase {name} failed: {msg}")


def _decode_teacher_forced(eng, prompt, steps):
    """Prefill + decode on fixed tokens; last-position logits of each."""
    out = [eng.prefill(prompt)]
    for t in range(steps.shape[1]):
        out.append(eng.decode_step(steps[:, t]))
    return np.stack([np.asarray(jax.block_until_ready(x)) for x in out], axis=1)


def _reference_logits(params, cfg, tokens, n_prompt, precision):
    """``transformer.forward`` logits at positions n_prompt-1 .. end-1."""
    with jax.default_matmul_precision(precision):
        logits, _ = forward(params, cfg, jnp.asarray(tokens))
        out = np.asarray(jax.block_until_ready(logits[:, n_prompt - 1:]))
    del logits
    return out


def phase_reference(model, cfg, params, adapter, sz, rng, dev, counter):
    g = 4
    b, s, n = sz["ref_batch"], sz["ref_prompt"], sz["ref_steps"]
    prompt = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
    steps = rng.integers(0, cfg.vocab_size, (b, n)).astype(np.int32)
    # every group selected and resident: selected attention == full attention
    all_groups = (s + n) // g + 2
    ecfg = EngineConfig(group_size=g, n_select=all_groups,
                        reuse_capacity=all_groups, rank=adapter.rank,
                        max_seq=s + n + g)
    with Phase("a:reference", dev, counter):
        with KVSwapEngine(model, params, ecfg, batch=b, adapter=adapter) as eng:
            got = _decode_teacher_forced(eng, prompt, steps)
        tokens = np.concatenate([prompt, steps], axis=1)
        want = _reference_logits(params, cfg, tokens, s, "highest")
        plain = _reference_logits(params, cfg, tokens, s, "default")
    err, rel = _err(got, want)
    base_err, base_rel = _err(plain, want)
    bound = max(REF_FACTOR * base_rel, REF_FLOOR)
    print(f"[a:reference] engine vs forward(highest): max abs {err:.6g}, "
          f"rel {rel:.6g}; forward(default) vs forward(highest): "
          f"max abs {base_err:.6g}, rel {base_rel:.6g}")
    _check("a:reference",
           np.isfinite(got).all() and got.shape == want.shape and rel <= bound,
           f"{b} rows x ({s}-token prefill + {n} decode steps), rel {rel:.6g} "
           f"<= bound {bound:.6g}")
    return dict(prompt=prompt, steps=steps, ecfg=ecfg, got=got, want=want,
                bound=bound)


def phase_served(model, cfg, params, adapter, sz, rng, dev, counter):
    g = 4
    shared = rng.integers(0, cfg.vocab_size, sz["shared"])
    prompts = []
    for i, n in enumerate(sz["served_prompts"]):
        if i in (0, 3):   # these two share the prefix
            tail = rng.integers(0, cfg.vocab_size, n - sz["shared"])
            prompts.append(np.concatenate([shared, tail]))
        else:
            prompts.append(rng.integers(0, cfg.vocab_size, n))
    max_new = sz["max_new"]
    ecfg = EngineConfig(group_size=g, n_select=sz["n_select"],
                        reuse_capacity=sz["reuse_capacity"], rank=adapter.rank,
                        max_seq=max(sz["served_prompts"]) + max_new + g)
    samplers = [_CheckedGreedy() for _ in prompts]
    schedule = dict(zip(sz["submit_at"], range(len(prompts))))
    with Phase("b:served", dev, counter):
        with PrefixCache(PrefixCacheConfig(block_tokens=32)) as pc, \
                ServeSession(model, params, ecfg, slots=2, adapter=adapter,
                             prefix_cache=pc) as sess:
            step = 0
            rids = []
            while schedule or sess.has_work:
                if step in schedule:
                    i = schedule.pop(step)
                    rids.append(sess.submit(prompts[i], max_new,
                                            sampler=samplers[i]))
                sess.step()
                step += 1
            done = sess.drain()
            stats = sess.stats()
    print(f"[b:served] {step} scheduler steps, {stats['decode_steps']} decode "
          f"steps, disk read bytes {stats['read_bytes']}, reuse ratio "
          f"{stats['reuse_ratio']:.4f}, prefix hit rate "
          f"{stats['prefix_hit_rate']:.4f}; modeled (DiskSpec/ComputeSpec, "
          f"not measured): goodput {stats['goodput_tokens_per_s']:.3f} tok/s")
    for i, rid in enumerate(rids):
        r = done.get(rid)
        print(f"[b:served] request {rid}: prompt {len(prompts[i])}, cached "
              f"{r.cached_tokens if r else None}, generated "
              f"{len(r.output) if r else None}, finite logits {samplers[i].finite}")
    ok = (len(done) == len(prompts) and not sess.failed
          and all(len(done[rid].output) == max_new for rid in rids)
          and all(s.finite and s.rows == max_new for s in samplers)
          and stats["read_bytes"] > 0
          and done[rids[3]].cached_tokens == sz["shared"])
    _check("b:served", ok,
           f"{len(done)}/{len(prompts)} requests finished with finite logits, "
           f"disk reads {stats['read_bytes']} B, warm admission restored "
           f"{done[rids[3]].cached_tokens if rids[3] in done else 0} "
           f"of {sz['shared']} shared tokens")


def _lowered_has_mosaic(model, params, a_run, adapter):
    """Whether the Pallas decode block and predictor lower to Mosaic custom
    calls (a compiled kernel) rather than to the interpreter's loop."""
    cfg = model.cfg
    b = a_run["prompt"].shape[0]
    m = a_run["ecfg"].n_select
    g = a_run["ecfg"].group_size
    sd = jax.ShapeDtypeStruct
    kv = sd((b, m * g + g, cfg.n_kv_heads, cfg.head_dim), jnp.float32)
    dec = model.decode_block.lower(
        model, params, 0, sd((b, cfg.d_model), jnp.float32), sd((b,), jnp.int32),
        kv, kv, sd((b, m * g + g), jnp.bool_), use_pallas=True).as_text()
    pred = fused_predict_pallas.lower(
        sd((b, cfg.n_heads, cfg.head_dim), jnp.float32), adapter.per_head,
        sd((b, a_run["ecfg"].max_seq, adapter.rank), jnp.float32),
        sd((b,), jnp.int32), group_size=g, n_select=m).as_text()
    return "tpu_custom_call" in dec, "tpu_custom_call" in pred


def phase_pallas(model, cfg, params, adapter, a_run, rng, dev, counter):
    ecfg = dataclasses.replace(a_run["ecfg"], use_pallas=True)
    b = a_run["prompt"].shape[0]
    with Phase("c:pallas", dev, counter):
        with KVSwapEngine(model, params, ecfg, batch=b, adapter=adapter) as eng:
            got = _decode_teacher_forced(eng, a_run["prompt"], a_run["steps"])
            k_lr = eng.k_lr[0]
            valid = jnp.asarray(eng.row_valid.astype(np.int32))
            valid_groups = int(eng.row_valid.min()) // ecfg.group_size
            q_lr = jnp.asarray(rng.standard_normal(
                (b, cfg.n_heads, adapter.rank)), jnp.float32)
            scores = np.asarray(jax.block_until_ready(ops.lowrank_group_scores(
                q_lr, k_lr, valid, group_size=ecfg.group_size)))
            with jax.default_matmul_precision("highest"):
                want_scores = np.asarray(ref.lowrank_group_scores_ref(
                    q_lr, k_lr, valid, ecfg.group_size))
    on_tpu = dev.platform == "tpu"
    dec_mosaic, pred_mosaic = _lowered_has_mosaic(model, params, a_run, adapter)
    print(f"[c:pallas] lowered kernels: decode attention "
          f"{'Mosaic (compiled)' if dec_mosaic else 'interpreted'}, predictor "
          f"{'Mosaic (compiled)' if pred_mosaic else 'interpreted'}")
    _check("c:pallas", dec_mosaic == on_tpu and pred_mosaic == on_tpu,
           f"kernels compiled exactly when the backend is a TPU "
           f"(platform {dev.platform})")
    err_a, rel_a = _err(got, a_run["got"])
    err_r, rel_r = _err(got, a_run["want"])
    print(f"[c:pallas] vs engine (a): max abs {err_a:.6g}, rel {rel_a:.6g}; "
          f"vs forward(highest): max abs {err_r:.6g}, rel {rel_r:.6g}")
    _check("c:pallas",
           np.isfinite(got).all() and rel_a <= a_run["bound"]
           and rel_r <= a_run["bound"],
           f"rel vs (a) {rel_a:.6g} and vs reference {rel_r:.6g} "
           f"<= bound {a_run['bound']:.6g}")
    s_err, s_rel = _err(scores[:, :valid_groups], want_scores[:, :valid_groups])
    _check("c:pallas", s_rel <= SCORE_TOL,
           f"scoring kernel vs jnp oracle on the engine's K_lr: max abs "
           f"{s_err:.6g}, rel {s_rel:.6g} <= {SCORE_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="run every phase at qwen3_8b.smoke_config() on any device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if not args.tiny and dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"--tiny runs the rehearsal on this device", file=sys.stderr)
        return 1
    sz = SIZES["tiny" if args.tiny else "full"]
    cfg = qwen3_8b.smoke_config() if args.tiny else dataclasses.replace(
        qwen3_8b.config(), n_layers=sz["layers"])
    print(f"device: {dev.platform} / {dev.device_kind} x {len(jax.devices())}; "
          f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"heads {cfg.n_heads}/{cfg.n_kv_heads}x{cfg.head_dim}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}, float32, seed {args.seed}", flush=True)
    counter = _CompileCounter()
    try:
        rng = np.random.default_rng(args.seed)
        with Phase("setup", dev, counter):
            params = jax.block_until_ready(
                init_params(jax.random.PRNGKey(args.seed), cfg))
            model = TransformerAdapter(cfg)
            # offline adapter fit (KVSwap §3.2) from layer 0's K on a
            # calibration prompt, as the tuner would
            calib = rng.integers(0, cfg.vocab_size, (1, sz["calib_tokens"]))
            pos = jnp.arange(sz["calib_tokens"])[None]
            _, k_cal, _ = model.prefill_block(
                params, 0, model.embed(params, jnp.asarray(calib)), pos)
            adapter = fit_adapter(np.asarray(k_cal), rank=64)
        a_run = phase_reference(model, cfg, params, adapter, sz, rng, dev, counter)
        phase_served(model, cfg, params, adapter, sz, rng, dev, counter)
        phase_pallas(model, cfg, params, adapter, a_run, rng, dev, counter)
    finally:
        counter.close()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    sys.exit(main())
