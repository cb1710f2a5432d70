"""Disaggregated prefill/decode vs co-located serving under a prefill burst.

The co-located :class:`~repro.serving.api.ServeSession` admits and decodes
on one modeled clock: a burst of long-document prefills lands *between*
the decode steps of running interactive requests, and every admission's
``modeled_seconds`` stretches their inter-token gaps — decode TPOT tails
absorb prefill compute.  Disaggregation
(:class:`~repro.disagg.DisaggFrontEnd`) moves prefill onto dedicated
engines whose clocks overlap the decode pool's by construction; the
decode session admits by **restoring the published chain** from the
shared :class:`~repro.cache.PrefixCache`, so an admission on the decode
clock costs a (planned, sequential) restore read instead of a full
prefill — the decode TPOT tail stays flat through the burst.

This harness replays one merged **doc-burst + chat** trace (the same
seed-deterministic requests, re-ridded by arrival) through

* ``solo``     — every request alone in a fresh one-slot session (the
  bit-identity reference),
* ``baseline`` — one co-located session (+ its own prefix cache, so the
  only delta vs disagg is *where* prefill runs),
* ``disagg``   — 2 prefill engines + 1 decode session over one shared
  cache,

for disk ∈ {nvme, ufs} (``--tiny``: nvme), all at ``kv_bits=16`` — the
restore-is-bit-identical regime, so every mode must emit the same tokens.

Asserted invariants (the run fails otherwise):

* **disagg decode TPOT p95 strictly better than co-located** on every
  disk (the headline);
* tokens bit-identical per request across solo / baseline / disagg;
* per-request warm-restore coverage: every disagg admission restored
  exactly the full published blocks of its prompt
  (``restored_tokens == ((S-1) // block_tokens) * block_tokens``);
* every trace request completes in every mode; no ticket failures, no
  re-prefills, no shed submissions.

    PYTHONPATH=src python -m benchmarks.disagg_serving [--tiny]
"""

from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from benchmarks.common import write_bench_json  # noqa: F401  (src/ bootstrap)

EPS = 1e-9
BLOCK_TOKENS = 32


def build_model():
    import jax

    from repro.models.transformer import ModelConfig, init_params

    # the slo_trace model: small enough to prefill on CPU in seconds, big
    # enough that modeled prefill compute (ORIN_NANO roofline) dominates a
    # same-length restore read — the regime disaggregation exploits
    cfg = ModelConfig(name="disagg-bench", arch_type="dense", n_layers=2,
                      d_model=128, n_heads=4, n_kv_heads=1, head_dim=16,
                      d_ff=1024, vocab_size=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def base_engine_cfg(max_seq: int):
    from repro.core.engine import EngineConfig

    # kv_bits=16: prefix restores are bit-identical to cold prefill, so
    # all three modes must agree token-for-token
    return EngineConfig(group_size=4, n_select=20, rank=16,
                        reuse_capacity=12, max_seq=max_seq, kv_bits=16,
                        predict_from="self", compute="jetson-orin-nano")


def merge_traces(name: str, *traces):
    """One trace from many: requests pooled, sorted by arrival, re-ridded.
    SLO classes are unioned (same-name classes must agree upstream)."""
    from repro.serving.trace import Trace

    classes, reqs = {}, []
    for tr in traces:
        classes.update(tr.slo_classes)
        reqs.extend(tr.requests)
    reqs.sort(key=lambda r: (r.arrival, r.rid))
    reqs = [dataclasses.replace(r, rid=i) for i, r in enumerate(reqs)]
    return Trace(workload=name, seed=traces[0].seed,
                 vocab_size=traces[0].vocab_size, slo_classes=classes,
                 requests=reqs)


def make_session(cfg, params, calib, ecfg, *, slots, prefix_cache=None):
    from repro.models.transformer import TransformerAdapter
    from repro.serving.api import ServeSession

    return ServeSession(TransformerAdapter(cfg), params, ecfg, slots=slots,
                        calib_k=calib, prefix_cache=prefix_cache)


def run_solo(cfg, params, calib, ecfg, trace) -> dict[int, list[int]]:
    """Every request alone in a fresh session: the reference tokens."""
    out = {}
    for r in trace.requests:
        with make_session(cfg, params, calib, ecfg, slots=1) as sess:
            rid = sess.submit(r.materialize(trace.vocab_size), r.max_new)
            sess.drain()
            out[r.rid] = sess.completed[rid].output.tolist()
    return out


def run_baseline(cfg, params, calib, ecfg, trace, *, slots) -> dict:
    """Co-located session with its own prefix cache (same cache policy as
    disagg — the only delta is where prefill runs)."""
    from repro.cache import PrefixCache, PrefixCacheConfig
    from repro.serving.trace import replay

    with PrefixCache(PrefixCacheConfig(block_tokens=BLOCK_TOKENS)) as pc:
        with make_session(cfg, params, calib, ecfg, slots=slots,
                          prefix_cache=pc) as sess:
            m = replay(trace, sess)
            m["tokens"] = {rid: req.output.tolist()
                           for rid, req in sess.completed.items()}
            return m


def run_disagg(cfg, params, calib, ecfg, trace, *, slots,
               n_prefill) -> dict:
    from repro.cache import PrefixCache, PrefixCacheConfig
    from repro.disagg import DisaggFrontEnd, PrefillEngine
    from repro.models.transformer import TransformerAdapter

    adapter = TransformerAdapter(cfg)
    with PrefixCache(PrefixCacheConfig(block_tokens=BLOCK_TOKENS)) as pc:
        prefills = [PrefillEngine(f"p{i}", adapter, params, ecfg, cache=pc,
                                  calib_k=calib) for i in range(n_prefill)]
        decode = make_session(cfg, params, calib, ecfg, slots=slots,
                              prefix_cache=pc)
        with DisaggFrontEnd(prefills, [decode], cache=pc) as front:
            m = front.replay(trace)
            m["tokens"] = {rid: out.tolist()
                           for rid, out in front.results().items()}
            return m


def check_invariants(out: dict, trace) -> list[str]:
    failures = []
    n = trace.n_requests
    prompt_tokens = {r.rid: r.prompt_tokens for r in trace.requests}
    solo = out["solo_tokens"]
    for disk, cell in out["disks"].items():
        base, dis = cell["baseline"], cell["disagg"]
        for mode, m in (("baseline", base), ("disagg", dis)):
            if m["requests"] != n:
                failures.append(f"{disk}/{mode}: completed {m['requests']} "
                                f"of {n} requests")
            for rid, toks in solo.items():
                got = m["tokens"].get(rid)
                if got != toks:
                    failures.append(f"{disk}/{mode}: request {rid} tokens "
                                    f"differ from solo reference")
                    break
        fleet = dis["fleet"]
        if fleet["ticket_failures"] or fleet["requeues"] \
                or fleet["handoff_rejections"]:
            failures.append(
                f"{disk}/disagg: unexpected fault-path activity "
                f"(failures={fleet['ticket_failures']}, "
                f"requeues={fleet['requeues']}, "
                f"shed={fleet['handoff_rejections']})")
        # the headline: decode TPOT p95 strictly better disaggregated
        if not dis["tpot"]["p95"] < base["tpot"]["p95"] - EPS:
            failures.append(
                f"{disk}: disagg TPOT p95 {dis['tpot']['p95']:.6f}s not "
                f"strictly better than co-located {base['tpot']['p95']:.6f}s")
        # per-request warm-restore coverage at the decode boundary
        for rec in dis["per_request"]:
            s = prompt_tokens[rec["rid"]]
            want = ((s - 1) // BLOCK_TOKENS) * BLOCK_TOKENS
            if rec["restored_tokens"] != want:
                failures.append(
                    f"{disk}/disagg: request {rec['rid']} restored "
                    f"{rec['restored_tokens']} of expected {want} tokens "
                    f"(prompt {s})")
    return failures


def main(tiny: bool = False) -> None:
    from repro.serving.metrics import SLOClass
    from repro.serving.trace import chat_trace, doc_trace

    cfg, params = build_model()
    rng = np.random.default_rng(0)
    calib = rng.standard_normal((256, cfg.n_kv_heads, cfg.head_dim)
                                ).astype(np.float32)
    slots = 2 if tiny else 3
    n_prefill = 2
    conversations, turns = (2, 2) if tiny else (3, 3)
    n_docs = 4 if tiny else 6
    sys_tokens, user_tokens, chat_new = 112, 16, 12
    ecfg = base_engine_cfg(max_seq=320)

    slo_classes = {
        "interactive": SLOClass("interactive", ttft_s=0.5, tpot_s=0.05),
        "batch": SLOClass("batch", ttft_s=2.0, tpot_s=0.1),
    }
    # chat turns paced so conversations overlap the doc burst; docs arrive
    # nearly back-to-back (the burst the co-located decode clock absorbs)
    chat = chat_trace(11, conversations=conversations, turns=turns,
                      sys_tokens=sys_tokens, user_tokens=user_tokens,
                      max_new=chat_new, turn_gap_s=0.02, conv_gap_s=0.005,
                      slo_classes=slo_classes, vocab_size=cfg.vocab_size)
    docs = doc_trace(12, n_requests=n_docs, doc_tokens=(240,), max_new=6,
                     interarrival_s=0.002, slo_classes=slo_classes,
                     vocab_size=cfg.vocab_size)
    # drop the burst into the middle of the chat phase
    mid = (chat.requests[-1].arrival if chat.requests else 0.0) * 0.3
    docs.requests = [dataclasses.replace(r, arrival=round(r.arrival + mid, 9))
                     for r in docs.requests]
    trace = merge_traces("docburst+chat", chat, docs)

    disks = ("nvme",) if tiny else ("nvme", "ufs")
    out = {
        "model": dataclasses.asdict(cfg),
        "engine": {"base": dataclasses.asdict(ecfg), "slots": slots,
                   "n_prefill": n_prefill, "block_tokens": BLOCK_TOKENS},
        "trace": {"workload": trace.workload, "n_requests": trace.n_requests,
                  "n_chat": chat.n_requests, "n_docs": len(docs.requests)},
        "disks": {},
    }
    print("disk,mode,tpot_p95_ms,tpot_p50_ms,ttft_p95_ms,makespan_s")
    # tokens depend only on prompt + sampling, never on the disk model, so
    # one solo pass (at the first disk) references every cell
    solo_ecfg = dataclasses.replace(ecfg, disk=disks[0])
    out["solo_tokens"] = run_solo(cfg, params, calib, solo_ecfg, trace)
    for disk in disks:
        dcfg = dataclasses.replace(ecfg, disk=disk)
        cell = out["disks"][disk] = {}
        for mode, run in (("baseline", lambda: run_baseline(
                               cfg, params, calib, dcfg, trace, slots=slots)),
                          ("disagg", lambda: run_disagg(
                               cfg, params, calib, dcfg, trace, slots=slots,
                               n_prefill=n_prefill))):
            m = run()
            cell[mode] = m
            makespan = (m["fleet"]["makespan_s"] if "fleet" in m
                        else m["makespan_seconds"])
            print(f"{disk},{mode},{m['tpot']['p95'] * 1e3:.3f},"
                  f"{m['tpot']['p50'] * 1e3:.3f},"
                  f"{m['ttft']['p95'] * 1e3:.3f},{makespan:.3f}")

    failures = check_invariants(out, trace)
    out["invariants_ok"] = not failures
    # the artifact keeps aggregates; tokens and per-request rows are bulky
    for cell in out["disks"].values():
        for m in cell.values():
            m.pop("tokens", None)
            m.pop("per_request", None)
    del out["solo_tokens"]
    write_bench_json("disagg_serving", out, tiny=tiny)
    if failures:
        raise SystemExit("disagg invariants failed:\n  "
                         + "\n  ".join(failures))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke mode: nvme only, smaller trace")
    main(tiny=ap.parse_args().tiny)
