"""Continuous batching vs the static batcher on a Poisson arrival trace.

Goodput A/B for the serving API redesign: the same mixed-length request
trace (Poisson arrivals, mixed prompt lengths, mixed ``max_new``) is served
two ways through the *same* persistent-engine machinery:

* **continuous** — :class:`repro.serving.api.ServeSession` as designed:
  per-slot admission the moment a slot frees up, per-request retirement the
  moment a request finishes (a retired slot charges zero further IO);
* **static** — the legacy ``BatchServer.flush()`` discipline, emulated on
  the session so both arms share one engine implementation: requests are
  ganged into batches of ``slots`` in arrival order, a batch starts only
  when its **last** member has arrived and the previous batch finished,
  short batches are padded with clone rows that burn real disk reads, and
  every row decodes to the batch-max ``max_new`` before truncating.

Goodput counts **completed-request tokens per modeled second** — each
request contributes exactly its own ``max_new``; the clock is the modeled
DiskSpec + ComputeSpec time (admission prefill seconds + pipelined decode
seconds).  The continuous arm must win on both nvme and emmc or this
benchmark fails the run.

    PYTHONPATH=src python -m benchmarks.continuous_serving [--tiny] \
        [--trace obs_trace.json]

``--trace PATH`` attaches an :class:`repro.obs.Observability` handle to the
first continuous run and exports its dual-clock Perfetto trace to PATH —
the artifact CI uploads from the tiny smoke.
"""

from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from benchmarks.common import write_bench_json  # noqa: F401  (src/ bootstrap)


def build_model():
    import jax

    from repro.models.transformer import ModelConfig, init_params

    cfg = ModelConfig(name="serve-bench", arch_type="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                      d_ff=128, vocab_size=211)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def build_trace(rng, *, n_requests, prompt_lo, prompt_hi, gen_lo, gen_hi,
                mean_interarrival):
    """Mixed-length requests with Poisson (exponential-gap) arrivals."""
    reqs = []
    t = 0.0
    for _ in range(n_requests):
        t += float(rng.exponential(mean_interarrival))
        reqs.append({
            "prompt_len": int(rng.integers(prompt_lo, prompt_hi + 1)),
            "max_new": int(rng.integers(gen_lo, gen_hi + 1)),
            "arrival": t,
        })
    return reqs


def _session(cfg, params, ecfg, slots, calib, obs=None):
    from repro.models.transformer import TransformerAdapter
    from repro.serving.api import ServeSession

    return ServeSession(TransformerAdapter(cfg), params, ecfg, slots=slots,
                        calib_k=calib, obs=obs)


def run_continuous(cfg, params, ecfg, slots, calib, trace, prompts,
                   obs=None) -> dict:
    with _session(cfg, params, ecfg, slots, calib, obs=obs) as sess:
        for r, p in zip(trace, prompts):
            sess.submit(p, r["max_new"], arrival=r["arrival"])
        done = sess.drain()
        tokens = sum(len(q.output) for q in done.values())
        snap = sess.engine.accountant.snapshot()
        return {"tokens": tokens, "makespan": sess.now,
                "goodput": tokens / sess.now,
                "read_bytes": snap["read_bytes"],
                "decode_steps": len(sess.engine.step_log)}


def run_static(cfg, params, ecfg, slots, calib, trace, prompts) -> dict:
    """Legacy flush discipline on the same engine machinery (see module
    docstring): gang-scheduled batches, clone padding, decode-to-batch-max."""
    with _session(cfg, params, ecfg, slots, calib) as sess:
        useful = 0
        for i in range(0, len(trace), slots):
            batch = trace[i:i + slots]
            bprompts = list(prompts[i:i + slots])
            # the flush can only start once the whole batch has arrived
            sess.now = max(sess.now, max(r["arrival"] for r in batch))
            batch_max = max(r["max_new"] for r in batch)
            while len(bprompts) < slots:        # clone padding burns real IO
                bprompts.append(bprompts[0])
            for p in bprompts:
                sess.submit(p, batch_max)       # everyone rides to batch max
            sess.drain()
            useful += sum(r["max_new"] for r in batch)
        snap = sess.engine.accountant.snapshot()
        return {"tokens": useful, "makespan": sess.now,
                "goodput": useful / sess.now,
                "read_bytes": snap["read_bytes"],
                "decode_steps": len(sess.engine.step_log)}


def main(tiny: bool = False, trace_path: str | None = None) -> None:
    from repro.core.engine import EngineConfig
    from repro.obs import Observability

    cfg, params = build_model()
    rng = np.random.default_rng(0)
    calib = rng.standard_normal((128, cfg.n_kv_heads, cfg.head_dim))
    slots = 2 if tiny else 4
    n_requests = 6 if tiny else 24
    prompt_lo, prompt_hi = (12, 24) if tiny else (16, 48)
    gen_lo, gen_hi = (2, 6) if tiny else (4, 16)
    max_seq = prompt_hi + gen_hi + 8
    ecfg = EngineConfig(group_size=4, n_select=max_seq // 8, rank=16,
                        reuse_capacity=max_seq // 8, max_seq=max_seq,
                        predict_from="self")

    # calibrate the arrival rate to the modeled service rate: one solo
    # request measures prefill + per-token seconds, then the trace targets
    # ~80 % utilization of the slot pool
    with _session(cfg, params, ecfg, slots, calib) as probe:
        probe.submit(rng.integers(0, cfg.vocab_size, prompt_hi), gen_hi)
        probe.drain()
        service = probe.now / gen_hi
    mean_interarrival = 0.8 * service * (gen_lo + gen_hi) / 2 / slots

    trace = build_trace(rng, n_requests=n_requests, prompt_lo=prompt_lo,
                        prompt_hi=prompt_hi, gen_lo=gen_lo, gen_hi=gen_hi,
                        mean_interarrival=mean_interarrival)
    prompts = [rng.integers(0, cfg.vocab_size, r["prompt_len"]) for r in trace]

    out = {"slots": slots, "n_requests": n_requests,
           "mean_interarrival_s": mean_interarrival, "disks": {}}
    print("disk,arm,goodput_tok_s,makespan_s,read_MB,decode_steps")
    ok = True
    obs = Observability() if trace_path else None
    for disk in ("nvme", "emmc"):
        dcfg = dataclasses.replace(ecfg, disk=disk)
        cont = run_continuous(cfg, params, dcfg, slots, calib, trace, prompts,
                              obs=obs)
        if obs is not None:       # trace the first continuous run only
            obs.export_trace(trace_path)
            print(f"wrote {trace_path}")
            obs = None
        stat = run_static(cfg, params, dcfg, slots, calib, trace, prompts)
        speedup = cont["goodput"] / stat["goodput"]
        out["disks"][disk] = {"continuous": cont, "static": stat,
                              "goodput_speedup": speedup}
        for arm, r in (("continuous", cont), ("static", stat)):
            print(f"{disk},{arm},{r['goodput']:.1f},{r['makespan']:.4f},"
                  f"{r['read_bytes'] / 1e6:.2f},{r['decode_steps']}")
        print(f"{disk},speedup,{speedup:.2f}x,,,")
        ok &= speedup > 1.0

    write_bench_json("continuous_serving", out, tiny=tiny)
    if not ok:
        raise SystemExit("continuous batching did not beat the static "
                         "batcher on every disk")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke mode: small trace")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export a Perfetto trace of the first continuous "
                         "run to PATH")
    args = ap.parse_args()
    main(tiny=args.tiny, trace_path=args.trace)
