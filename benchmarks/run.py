# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark aggregator — one entry per paper table/figure.

| entry              | paper artifact                  |
|--------------------|---------------------------------|
| tab4_throughput    | Tab. 4 / App. Tab. 2            |
| tab2_quality       | Tabs. 2/3 + Fig. 9 (NIAH)       |
| fig12_group_size   | Fig. 12 (G ablation)            |
| tab5_reuse         | Tab. 5 (reuse stats)            |
| fig13a_latency     | Fig. 13a (latency breakdown)    |
| fig13b_selection   | Fig. 13b (MG ablation)          |
| fig1_fig3a_memory  | Figs. 1 + 3a (memory)           |
| appA_tuner         | §3.5 / App. A (parameter tuner) |
| roofline           | §Roofline (from dry-run output) |
"""

import sys
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    from benchmarks import (ablation_group, ablation_selection, e2e_perplexity,
                            latency_breakdown, memory_footprint, quality_niah,
                            reuse, roofline, shardmap_ab, throughput,
                            tuner_demo)
    modules = [throughput, quality_niah, e2e_perplexity, ablation_group, reuse,
               latency_breakdown, ablation_selection, memory_footprint,
               tuner_demo, roofline, shardmap_ab]
    print("name,us_per_call,derived")
    failures = 0
    for mod in modules:
        try:
            mod.main()
        except Exception:  # noqa: BLE001 — keep the suite running
            failures += 1
            print(f"{mod.__name__},0,FAILED")
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
