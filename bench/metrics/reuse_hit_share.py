"""Tiers: reuse-buffer hits over hits plus misses in the window, summed
over layers (the counters behind KVSwapEngine.reuse_ratio())."""


def read(run):
    hits = run.after["reuse_hits"] - run.before["reuse_hits"]
    misses = run.after["reuse_misses"] - run.before["reuse_misses"]
    return 100.0 * hits / (hits + misses) if hits + misses else None
