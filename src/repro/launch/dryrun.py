"""Multi-pod dry-run: prove every (architecture × input-shape × mesh) lowers
and compiles, and extract the roofline terms from the compiled artifact.

Run:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape decode_32k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out exp.json]

Run as a script it asks for 512 placeholder CPU devices before JAX is
imported (JAX fixes the device count at first init), unless the caller set
``XLA_FLAGS`` already; importing the module leaves ``XLA_FLAGS`` alone.
"""

import os

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import dataclasses
import json
import re
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.configs.shapes import SHAPES, InputShape
from repro.launch.mesh import make_production_mesh
from repro.serving import decode as D
from repro.serving.decode import KVSwapServeConfig
from repro.sharding import partition as SP
from repro.training.optim import AdamWConfig, adamw_init, adamw_update
from repro.training.train import softmax_xent

# Architectures whose weights are too large to replicate across the data
# axis — FSDP (shard over 'data') for all modes, not just training.
FSDP_ALWAYS = {"llama4-maverick-400b-a17b"}

# KVSwap serving defaults for decode shapes (paper: MG = 400, G = 4).
SERVE_KVSWAP = KVSwapServeConfig(group_size=4, n_select=100, rank=64)


# ---------------------------------------------------------------------------
# sharding helpers
# ---------------------------------------------------------------------------

def _fsdp_spec(spec: P) -> P:
    """Add 'data' sharding to the largest replicated dim of a weight spec."""
    parts = list(spec)
    if "data" in parts:
        return spec
    for i, p in enumerate(parts):
        if p is None:
            parts[i] = "data"
            return P(*parts)
    return spec


def param_shardings(params_shape, mesh, *, fsdp: bool):
    specs = SP.param_pspecs(params_shape, mesh)
    if fsdp:
        specs = jax.tree_util.tree_map(
            _fsdp_spec, specs, is_leaf=lambda x: isinstance(x, P))
        specs = jax.tree_util.tree_map(
            lambda sp, leaf: SP.sanitize_spec(sp, getattr(leaf, "shape", ()), mesh),
            specs, params_shape, is_leaf=lambda x: isinstance(x, P))
    return SP.to_named_shardings(mesh, specs)


# ---------------------------------------------------------------------------
# step builders: (fn, abstract args, in_shardings)
# ---------------------------------------------------------------------------

def build_train(cfg, shape: InputShape, mesh, *, fsdp: bool):
    is_whisper = registry.is_whisper(cfg)
    dp = SP.batch_axes(mesh)
    params_shape = jax.eval_shape(
        lambda k: registry.init_params(k, cfg, jnp.bfloat16), jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(adamw_init, params_shape)
    opt_cfg = AdamWConfig()

    if is_whisper:
        from repro.models import whisper as W

        def loss_fn(params, batch):
            enc = W.encode(params, cfg, batch["frames"])
            logits, _ = W.decoder_forward(params, cfg, batch["tokens"], enc)
            return softmax_xent(logits, batch["targets"])
    else:
        from repro.models import transformer as T

        def loss_fn(params, batch):
            logits, aux = T.forward(params, cfg, batch["tokens"])
            loss = softmax_xent(logits, batch["targets"])
            return loss + 0.01 * aux if cfg.n_experts else loss

    def train_step(params, opt, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt = adamw_update(params, grads, opt, opt_cfg)
        return params, opt, loss

    b, s = shape.global_batch, shape.seq_len
    batch_shape = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "targets": jax.ShapeDtypeStruct((b, s), jnp.int32),
    }
    batch_spec = {"tokens": P(dp, None), "targets": P(dp, None)}
    if is_whisper:
        batch_shape["frames"] = jax.ShapeDtypeStruct((b, cfg.enc_frames, cfg.d_model), jnp.bfloat16)
        batch_spec["frames"] = P(dp, None, None)

    p_shard = param_shardings(params_shape, mesh, fsdp=True)  # train always FSDP
    o_shard = jax.eval_shape(adamw_init, params_shape)
    o_shard = param_shardings(opt_shape, mesh, fsdp=True)
    # AdamW step counter is a scalar — replicate
    o_shard = o_shard._replace(step=NamedSharding(mesh, P()))
    b_shard = jax.tree_util.tree_map(lambda sp: NamedSharding(mesh, sp), batch_spec,
                                     is_leaf=lambda x: isinstance(x, P))
    args = (params_shape, opt_shape, batch_shape)
    shardings = (p_shard, o_shard, b_shard)
    return train_step, args, shardings


def build_prefill(cfg, shape: InputShape, mesh, *, fsdp: bool, kvswap: bool):
    is_whisper = registry.is_whisper(cfg)
    dp = SP.batch_axes(mesh)
    b, s = shape.global_batch, shape.seq_len
    scfg = SERVE_KVSWAP if kvswap else None

    def make_params_shape(k):
        p = registry.init_params(k, cfg, jnp.bfloat16)
        if scfg is not None:
            p = D.attach_kvswap_adapters(k, p, cfg, scfg.rank, jnp.bfloat16)
        return p

    params_shape = jax.eval_shape(make_params_shape, jax.random.PRNGKey(0))
    cache_shape = jax.eval_shape(
        lambda: D.init_cache(cfg, b, s, dtype=jnp.bfloat16, kvswap=scfg))

    if is_whisper:
        def step(params, tokens, cache, enc_out):
            return D.prefill(params, cfg, tokens, cache, kvswap=scfg, enc_out=enc_out)
        args = (params_shape,
                jax.ShapeDtypeStruct((b, s), jnp.int32),
                cache_shape,
                jax.ShapeDtypeStruct((b, cfg.enc_frames, cfg.d_model), jnp.bfloat16))
        extra_spec = (NamedSharding(mesh, P(dp, None, None)),)
    else:
        def step(params, tokens, cache):
            return D.prefill(params, cfg, tokens, cache, kvswap=scfg)
        args = (params_shape, jax.ShapeDtypeStruct((b, s), jnp.int32), cache_shape)
        extra_spec = ()

    cache_spec = SP.cache_pspecs(cfg, mesh, shard_seq=False, kvswap=kvswap)
    c_shard = jax.tree_util.tree_map(lambda sp: NamedSharding(mesh, sp), cache_spec,
                                     is_leaf=lambda x: isinstance(x, P))
    shardings = (param_shardings(params_shape, mesh, fsdp=fsdp),
                 NamedSharding(mesh, P(dp, None)), c_shard) + extra_spec
    return step, args, shardings


def build_decode(cfg, shape: InputShape, mesh, *, fsdp: bool, kvswap: bool,
                 seq_over_model: bool = False, rolling: bool = False):
    is_whisper = registry.is_whisper(cfg)
    dp = SP.batch_axes(mesh)
    b, s = shape.global_batch, shape.seq_len
    shard_seq = b == 1                     # long_500k: context parallelism
    scfg = None
    if kvswap:
        scfg = dataclasses.replace(SERVE_KVSWAP, rolling=rolling)

    def make_params_shape(k):
        p = registry.init_params(k, cfg, jnp.bfloat16)
        if scfg is not None:
            p = D.attach_kvswap_adapters(k, p, cfg, scfg.rank, jnp.bfloat16)
        return p

    params_shape = jax.eval_shape(make_params_shape, jax.random.PRNGKey(0))
    cache_shape = jax.eval_shape(
        lambda: D.init_cache(cfg, b, s, dtype=jnp.bfloat16, kvswap=scfg))

    if is_whisper:
        def step(params, tokens, cache, enc_out):
            return D.serve_step(params, cfg, tokens, cache, kvswap=scfg, enc_out=enc_out)
        args = (params_shape,
                jax.ShapeDtypeStruct((b, 1), jnp.int32),
                cache_shape,
                jax.ShapeDtypeStruct((b, cfg.enc_frames, cfg.d_model), jnp.bfloat16))
        extra_spec = (NamedSharding(mesh, P(None if shard_seq else dp, None, None)),)
    else:
        def step(params, tokens, cache):
            return D.serve_step(params, cfg, tokens, cache, kvswap=scfg)
        args = (params_shape, jax.ShapeDtypeStruct((b, 1), jnp.int32), cache_shape)
        extra_spec = ()

    cache_spec = SP.cache_pspecs(cfg, mesh, shard_seq=shard_seq, kvswap=kvswap,
                                 seq_over_model=seq_over_model, rolling=rolling)
    c_shard = jax.tree_util.tree_map(lambda sp: NamedSharding(mesh, sp), cache_spec,
                                     is_leaf=lambda x: isinstance(x, P))
    tok_spec = P() if shard_seq else P(dp, None)
    shardings = (param_shardings(params_shape, mesh, fsdp=fsdp),
                 NamedSharding(mesh, tok_spec), c_shard) + extra_spec
    return step, args, shardings


def uses_kvswap(cfg) -> bool:
    """KVSwap selection applies iff the arch has softmax-attention KV."""
    if registry.is_whisper(cfg):
        return True
    return any(k in ("attn", "moe_attn", "shared_attn") for k in cfg.blocks)


# ---------------------------------------------------------------------------
# collective parsing (roofline collective term)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(tok: str) -> int:
    m = _SHAPE_RE.match(tok)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in optimized HLO text."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        for coll in _COLLECTIVES:
            marker = f" {coll}("
            idx = line.find(marker)
            if idx < 0:
                # fused variants e.g. all-gather-start(
                marker = f" {coll}-start("
                idx = line.find(marker)
                if idx < 0:
                    continue
            args = line[idx + len(marker):]
            depth = 1
            end = 0
            for i, ch in enumerate(args):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            operands = args[:end]
            nbytes = sum(_shape_bytes(t.strip()) for t in operands.split(",") if "[" in t)
            if nbytes == 0:
                # operand shapes elided: fall back to result shape
                pre = line[:idx].strip()
                eq = pre.rfind("=")
                if eq >= 0:
                    res = pre[eq + 1:].strip().split()[0]
                    nbytes = _shape_bytes(res)
            out[coll] += nbytes
            counts[coll] += 1
            break
    out["_counts"] = counts
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


# ---------------------------------------------------------------------------
# one dry-run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DryrunResult:
    arch: str
    shape: str
    mesh: str
    kvswap: bool
    ok: bool = False
    error: str = ""
    lower_s: float = 0.0
    compile_s: float = 0.0
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collective_bytes: int = 0
    collectives: dict = dataclasses.field(default_factory=dict)
    memory: dict = dataclasses.field(default_factory=dict)


def run_one(arch_id: str, shape_name: str, *, multi_pod: bool = False,
            kvswap: bool | None = None, verbose: bool = True,
            donate: bool = True, moe_pspecs: bool = True,
            seq_over_model: bool = False, rolling: bool = False,
            seq_parallel: bool = False) -> DryrunResult:
    """One dry-run.  ``donate`` aliases the cache (decode/prefill) and the
    params+opt (train) so serve/train steps update state in place instead of
    copying it — §Perf iteration 1.  ``moe_pspecs`` pins the MoE dispatch
    buffer to P(data, model) — §Perf iteration 2."""
    from repro.models import layers as L
    from repro.models import transformer as T

    cfg = registry.get(arch_id)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    fsdp = arch_id in FSDP_ALWAYS
    if kvswap is None:
        kvswap = shape.kind == "decode" and uses_kvswap(cfg)
    res = DryrunResult(arch=arch_id, shape=shape_name,
                       mesh="2x16x16" if multi_pod else "16x16", kvswap=kvswap)
    try:
        dp = SP.batch_axes(mesh)
        # Per-arch gating, empirically grounded (EXPERIMENTS.md §Perf):
        #  * MoE dispatch constraints help the big-d_model top-1 regime
        #    (llama4: 17x) but regress olmoe's top-8/64-small-ff regime and
        #    all decode shapes (1-token scatters) — gate on d_model and kind.
        #  * Sequence-parallel activations: 2-12x for dense/hybrid train and
        #    prefill; regress small-d_model MoE (resharding outweighs the
        #    all-reduce savings at d_model=2048 with top-8 dispatch).
        small_moe = (not registry.is_whisper(cfg) and cfg.n_experts
                     and cfg.d_model < 4096)
        if (moe_pspecs and not registry.is_whisper(cfg) and cfg.n_experts
                and shape.kind != "decode" and not small_moe):
            L.set_moe_pspecs({"buf": P(dp, None, None, None),
                              "y": P(dp, None, None)})
        else:
            L.set_moe_pspecs(None)
        #  * Seq-parallel is a train-side win (grad all-reduces) and a
        #    large-MoE prefill win; dense prefill regresses its (secondary)
        #    collective term — gate to train or large-MoE shapes.
        sp_applies = (shape.kind == "train"
                      or (cfg_has_moe := (not registry.is_whisper(cfg)
                                          and bool(cfg.n_experts))) and not small_moe)
        T.set_activation_pspec(
            P(dp, "model", None)
            if (seq_parallel and not registry.is_whisper(cfg) and not small_moe
                and sp_applies)
            else None)
        if shape.kind == "train":
            step, args, shardings = build_train(cfg, shape, mesh, fsdp=fsdp)
            donate_args = (0, 1) if donate else ()      # params, opt state
        elif shape.kind == "prefill":
            step, args, shardings = build_prefill(cfg, shape, mesh, fsdp=fsdp, kvswap=False)
            donate_args = (2,) if donate else ()        # cache
        else:
            step, args, shardings = build_decode(cfg, shape, mesh, fsdp=fsdp,
                                                 kvswap=kvswap,
                                                 seq_over_model=seq_over_model,
                                                 rolling=rolling and bool(kvswap))
            donate_args = (2,) if donate else ()        # cache
        t0 = time.time()
        with mesh:
            lowered = jax.jit(step, in_shardings=shardings,
                              donate_argnums=donate_args).lower(*args)
        res.lower_s = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        res.compile_s = time.time() - t0
        ma = compiled.memory_analysis()
        res.memory = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "code_bytes": int(getattr(ma, "generated_code_size_in_bytes", 0)),
        }
        ca = compiled.cost_analysis() or {}
        res.flops = float(ca.get("flops", 0.0))
        res.bytes_accessed = float(ca.get("bytes accessed", 0.0))
        colls = parse_collective_bytes(compiled.as_text())
        res.collective_bytes = int(colls["total"])
        res.collectives = {k: int(v) for k, v in colls.items() if k != "_counts" and not isinstance(v, dict)}
        res.ok = True
        if verbose:
            print(f"[ok] {arch_id} × {shape_name} × {res.mesh} kvswap={kvswap} "
                  f"lower={res.lower_s:.1f}s compile={res.compile_s:.1f}s "
                  f"flops={res.flops:.3e} coll={res.collective_bytes:.3e}B")
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        res.ok = False
        res.error = f"{type(e).__name__}: {e}"
        if verbose:
            print(f"[FAIL] {arch_id} × {shape_name} × {res.mesh}: {res.error[:300]}")
    finally:
        L.set_moe_pspecs(None)
        T.set_activation_pspec(None)
    return res


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=registry.list_archs())
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true", help="sweep all arch × shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--full-attn", action="store_true",
                    help="decode shapes without KVSwap selection (baseline)")
    ap.add_argument("--no-donate", action="store_true",
                    help="disable state donation (pre-optimization baseline)")
    ap.add_argument("--no-moe-pspecs", action="store_true",
                    help="disable MoE dispatch sharding constraints")
    ap.add_argument("--opt", action="store_true",
                    help="beyond-paper decode optimizations: seq-over-model "
                         "cache sharding + device rolling buffer (§Perf)")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    combos = []
    archs = registry.list_archs() if args.all else [args.arch]
    shapes = list(SHAPES) if args.all else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    results = []
    for a, s, mp in combos:
        kv = False if args.full_attn else None
        results.append(run_one(a, s, multi_pod=mp, kvswap=kv,
                               donate=not args.no_donate,
                               moe_pspecs=not args.no_moe_pspecs,
                               seq_over_model=args.opt, rolling=args.opt,
                               seq_parallel=args.opt))

    n_ok = sum(r.ok for r in results)
    print(f"\n{n_ok}/{len(results)} combinations lowered + compiled")
    if args.out:
        with open(args.out, "w") as f:
            json.dump([dataclasses.asdict(r) for r in results], f, indent=1)
        print(f"wrote {args.out}")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
