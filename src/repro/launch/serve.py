"""Serving launcher: batched decode through either serving path.

* ``--engine disk``   — the paper's runtime: KV on disk, grouped prediction,
  reuse buffer, modeled Jetson+NVMe/eMMC timing (repro.core).
* ``--engine device`` — the device-cache path: device KV cache + KVSwap
  selected attention (repro.serving.decode).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --engine disk --prompt-len 96 --gen-len 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.serving import decode as D
from repro.serving.decode import KVSwapServeConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=registry.list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=("disk", "device"), default="disk")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=96)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--disk", choices=("nvme", "ufs", "emmc"), default="nvme")
    ap.add_argument("--group-size", type=int, default=4)
    ap.add_argument("--n-select", type=int, default=8)
    ap.add_argument("--rank", type=int, default=16)
    args = ap.parse_args()

    cfg = registry.smoke(args.arch) if args.smoke else registry.get(args.arch)
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)).astype(np.int32)
    max_len = args.prompt_len + args.gen_len + args.group_size

    enc_out = None
    if registry.is_whisper(cfg):
        from repro.models import whisper as W
        frames = jax.random.normal(jax.random.PRNGKey(1),
                                   (args.batch, cfg.enc_frames, cfg.d_model))
        enc_out = W.encode(params, cfg, frames)

    if args.engine == "disk":
        from repro.core.engine import EngineConfig, KVSwapEngine
        adapter_model = registry.build_adapter(cfg)
        if enc_out is not None:
            adapter_model.set_encoder_output(params, enc_out)
        calib = rng.standard_normal((1024, cfg.n_kv_heads, cfg.head_dim))
        ecfg = EngineConfig(group_size=args.group_size, n_select=args.n_select,
                            rank=args.rank, reuse_capacity=4 * args.n_select,
                            max_seq=max_len, disk=args.disk)
        t0 = time.time()
        with KVSwapEngine(adapter_model, params, ecfg, batch=args.batch,
                          calib_k=calib) as eng:
            out = eng.generate(prompts, args.gen_len)
            print(f"tokens:\n{out}")
            print(f"wall, measured on {jax.devices()[0].device_kind}: "
                  f"{time.time() - t0:.1f}s (compiles included)")
            print(f"reuse ratio               : {eng.reuse_ratio():.2f}")
            print(f"modeled {args.disk} throughput: "
                  f"{eng.simulated_throughput():.1f} tok/s")
    else:
        scfg = KVSwapServeConfig(group_size=args.group_size,
                                 n_select=args.n_select, rank=args.rank)
        params = D.attach_kvswap_adapters(jax.random.PRNGKey(2), params, cfg, args.rank)
        cache = D.init_cache(cfg, args.batch, max_len, kvswap=scfg)
        logits, cache = D.prefill(params, cfg, jnp.asarray(prompts), cache,
                                  kvswap=scfg, enc_out=enc_out)
        step = jax.jit(lambda p, t, c: D.serve_step(p, cfg, t, c, kvswap=scfg,
                                                    enc_out=enc_out))
        toks = []
        t0 = time.time()
        for _ in range(args.gen_len):
            nxt = jnp.argmax(logits, -1)[:, None]
            toks.append(np.asarray(nxt[:, 0]))
            logits, cache = step(params, nxt, cache)
        dt = time.time() - t0
        print(f"tokens:\n{np.stack(toks, 1)}")
        print(f"device path: {args.gen_len * args.batch / dt:.1f} tok/s "
              f"(this host)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
