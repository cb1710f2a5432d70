import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import pytest  # noqa: E402

from bench import spec  # noqa: E402

# The committed cells at a size a CPU test can run: the same keys, layer
# kinds and engine paths, with every width and length cut down.
TINY_CONFIG = {
    "hidden_size": 256, "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 32,
    "intermediate_size": 512, "vocab_size": 512, "num_hidden_layers": 2,
    "calibration_tokens": 64,
    "engine": {"group_size": 4, "n_select": 16, "rank": 16, "reuse_capacity": 24,
               "kv_bits": 16, "warm_budget_bytes": 0},
}
# With 8 heads and 16 of 128 groups selected, head agreement 0.9 loses far
# more of each head's attention than at the published widths (32 heads,
# 100 of 2048 groups); 0.97 loses about as much here as 0.9 does there.
TINY_WEIGHTS = {"qk_read_dims": 64, "qk_rope_pairs": 4, "head_agreement": 0.97}
TINY_TRAFFIC = {"contexts": [256, 384, 512, 512], "turn_tokens": 128, "max_new": 512,
                "slots": 4, "max_seq": 1028, "prefix_budget_bytes": 64 << 20}

# The limit at this size, from CPU readings of four seeds (3000000077,
# 3000000021, 11, 12): sound runs read gap_mean 0.004-0.21; the float8
# control on 3000000077 reads 1.13.
TINY_CHECKS = {"sample_requests": 5, "min_checked_tokens": 10,
               "gap_mean": {"limit": 0.5}}


def tiny_cell(name: str) -> spec.Cell:
    cell = spec.cell(name)
    cfg = copy.deepcopy(cell.config)
    cfg.update(TINY_CONFIG)
    cfg["weights"] = {**cfg["weights"], **TINY_WEIGHTS}
    traffic = {**cell.traffic, **TINY_TRAFFIC}
    checks = {**cell.checks, **TINY_CHECKS}
    return spec.Cell(name=name, config=cfg, traffic=traffic, chips=1,
                     end_to_end=cell.end_to_end, per_layer=cell.per_layer, checks=checks)


@pytest.fixture
def tiny():
    return tiny_cell
