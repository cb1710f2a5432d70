"""Paper Tab. 5 — reuse ratio statistics + throughput uplift from reuse.

Runs the *real engine* (disk store + reuse buffer) on a trained tiny model to
measure reuse ratio, then the throughput model at paper scale for the
with/without-reuse uplift on both disks.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import LLAMA3_8B, Timer, emit
from repro.core import baselines as B
from repro.core.engine import EngineConfig, KVSwapEngine
from repro.core.offload import DISKS
from repro.models.transformer import ModelConfig, TransformerAdapter, init_params


def engine_reuse_ratio(n_inputs=4, n_steps=24) -> list[float]:
    cfg = ModelConfig(name="bench", arch_type="dense", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=97)
    params = init_params(jax.random.PRNGKey(0), cfg)
    adapter = TransformerAdapter(cfg)
    rng = np.random.default_rng(0)
    calib = rng.standard_normal((256, cfg.n_kv_heads, cfg.head_dim))
    ratios = []
    for i in range(n_inputs):
        prompt = rng.integers(0, 97, (1, 48)).astype(np.int32)
        ecfg = EngineConfig(group_size=4, n_select=6, rank=8,
                            reuse_capacity=16, max_seq=128)
        with KVSwapEngine(adapter, params, ecfg, batch=1, calib_k=calib) as eng:
            eng.generate(prompt, n_steps)
            ratios.append(eng.reuse_ratio())
    return ratios


def throughput_uplift() -> dict:
    out = {}
    hk, d = LLAMA3_8B.n_kv_heads, LLAMA3_8B.head_dim
    for disk_name, disk in DISKS.items():
        tps = {}
        for reuse in (True, False):
            pol = B.KVSwapPolicy(hk, d, group_size=4, rank=32, reuse=reuse)
            r = B.simulate_throughput(pol, disk=disk, dims=LLAMA3_8B, n_layers=32,
                                      batch=8, n_ctx=4096, budget_tokens=400, n_steps=8)
            tps[reuse] = r["tokens_per_s"]
        out[disk_name] = tps[True] / tps[False]
    return out


def main() -> str:
    with Timer() as t:
        ratios = engine_reuse_ratio()
        uplift = throughput_uplift()
    print(f"reuse_ratio min={min(ratios):.3f} max={max(ratios):.3f} "
          f"avg={np.mean(ratios):.3f} std={np.std(ratios):.3f}")
    print(f"tp_uplift nvme={uplift['nvme']:.1f}x emmc={uplift['emmc']:.1f}x")
    emit("tab5_reuse", t.us,
         f"avg_reuse={np.mean(ratios):.2f} uplift_nvme={uplift['nvme']:.1f}x "
         f"uplift_emmc={uplift['emmc']:.1f}x")
    return "ok"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
