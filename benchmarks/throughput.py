"""Paper Tab. 4 / App. Tab. 2 — decode throughput grid.

Replays the modeled Jetson+disk pipeline (DiskSpec + ComputeSpec + the
policies' real selection/I-O behaviour) across disk × batch × context-length,
for every offloading method.  The paper's MG=400 budget; per-batch KV budget
is the relaxed 1/13 setting.
"""

from __future__ import annotations


from benchmarks.common import LLAMA3_8B, Timer, emit
from repro.core import baselines as B
from repro.core.offload import DISKS


def policies(disk: str):
    hk, d = LLAMA3_8B.n_kv_heads, LLAMA3_8B.head_dim
    g = 8 if disk == "emmc" else 4
    return [
        B.FlexGenPolicy(hk, d),
        B.InfiniGenPolicy(hk, d),
        B.InfiniGenPolicy(hk, d, head_agg=True),
        B.InfiniGenPolicy(hk, d, head_agg=True, reuse=True),
        B.ShadowKVPolicy(hk, d, rank=160),
        B.ShadowKVPolicy(hk, d, rank=160, reuse=True),     # §7 "ShadowKV+reuse"
        B.LokiPolicy(hk, d, rank=32),
        B.KVSwapPolicy(hk, d, group_size=g, rank=32, reuse=True),
        B.KVSwapPolicy(hk, d, group_size=g, rank=32, reuse=True, kv_bytes=1),
    ]


def run(quick: bool = True) -> dict:
    batches = (1, 8) if quick else (1, 2, 4, 8, 16)
    cls = (16384, 32768) if quick else (8192, 16384, 24576, 32768)
    rows = []
    print("disk,policy,batch,context,tokens_per_s,io_ms,compute_ms")
    for disk_name, disk in DISKS.items():
        for cl in cls:
            for b in batches:
                for pol in policies(disk_name):
                    r = B.simulate_throughput(
                        pol, disk=disk, dims=LLAMA3_8B, n_layers=32, batch=b,
                        n_ctx=min(cl, 4096),  # selection trace length (I/O scales via budget)
                        budget_tokens=400, n_steps=8)
                    rows.append(dict(r, disk=disk_name, batch=b, context=cl))
                    print(f"{disk_name},{r['policy']},{b},{cl},"
                          f"{r['tokens_per_s']:.1f},{r['t_io']*1e3:.2f},{r['t_compute']*1e3:.2f}")
    return {"rows": rows}


def main() -> str:
    with Timer() as t:
        out = run(quick=True)
    rows = out["rows"]
    kv = [r for r in rows if r["policy"] == "kvswap" and r["disk"] == "nvme" and r["batch"] == 8]
    best = max(r["tokens_per_s"] for r in kv)
    emit("tab4_throughput", t.us, f"kvswap_nvme_b8={best:.1f}tok/s")
    return "ok"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
