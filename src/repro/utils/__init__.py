from repro.utils.bytesize import GiB, MiB, KiB
from repro.utils.stats import percentile, percentiles
from repro.utils.treeops import tree_bytes, tree_count

__all__ = ["GiB", "MiB", "KiB", "percentile", "percentiles", "tree_bytes",
           "tree_count"]
