"""Model and kernels: the least time the jitted decode_block calls in the
trace could take (bytes of the layer's weights and gathered K/V over HBM
bandwidth, or operations over peak, whichever is larger; at these shapes
bytes bound it) over their summed device time."""

from bench import costs

PROGRAM = "jit_decode_block"


def read(run):
    if run.trace is None or run.trace["program_s"].get(PROGRAM, 0.0) <= 0.0:
        return None
    eng, cfg, slots = run.engine, run.config, run.traffic["slots"]
    context = eng["n_select"] * eng["group_size"] + eng["group_size"]
    itemsize = 4 if cfg["torch_dtype"] == "float32" else 2
    least, _ = costs.roofline_seconds(
        costs.decode_block_flops(cfg, slots, context),
        costs.decode_block_bytes(cfg, slots, context, itemsize), run.peak)
    return 100.0 * run.trace["program_calls"][PROGRAM] * least / run.trace["program_s"][PROGRAM]
