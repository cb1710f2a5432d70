"""Paper §3.5 / App. A — offline parameter-tuning demonstration.

Runs the greedy solver for the paper's budgets and both disks; checks the
recovered settings against the paper's reported defaults (G=4 NVMe /
G=8-16 eMMC, MG=400, σ up to 32).
"""

from __future__ import annotations


from benchmarks.common import LLAMA3_8B, Timer, emit
from repro.core import tuner
from repro.utils import MiB


def run() -> dict:
    out = {}
    print("disk,budget,G,M,sigma,C,mem_mib,overlap")
    for disk in ("nvme", "emmc"):
        for budget, tag in ((310 * MiB, "relaxed"), (120 * MiB, "tight")):
            inp = tuner.TunerInputs(dims=LLAMA3_8B, n_layers=32, b_max=8,
                                    s_max=32768, budget_bytes=budget, disk=disk)
            t = tuner.solve(inp, reuse_table=tuner.build_reuse_table())
            out[f"{disk}_{tag}"] = t
            print(f"{disk},{tag},{t.group_size},{t.n_select},{t.sigma},"
                  f"{t.reuse_capacity},{t.mem_bytes / MiB:.0f},{t.meets_overlap}")
    return out


def main() -> str:
    with Timer() as t:
        out = run()
    nv = out["nvme_relaxed"]
    emit("appA_tuner", t.us,
         f"nvme_relaxed G={nv.group_size} sigma={nv.sigma} "
         f"in_budget={nv.mem_bytes <= 310 * MiB}")
    return "ok"


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
