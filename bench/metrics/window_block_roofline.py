"""Model and kernels: the least time the calls of the windowed layers'
decode program (``jit_decode_window_block``: attention over the device
ring and the expert layer) could take, over its device time.  Operations
and bytes of one call come from the configuration's family; nothing is
read where the family or the trace has no such program."""

from bench import spec

PROGRAM = "jit_decode_window_block"


def read(run):
    if run.trace is None:
        return None
    calls = run.trace["program_calls"].get(PROGRAM, 0)
    busy = run.trace["program_s"].get(PROGRAM, 0.0)
    if not calls or busy <= 0.0:
        return None
    eng, cfg = run.engine, run.config
    context = eng["n_select"] * eng["group_size"] + eng["group_size"]
    itemsize = 4 if cfg["torch_dtype"] == "float32" else 2
    programs = spec.family(cfg["family"]).decode_programs(cfg, run.traffic["slots"], context,
                                                          itemsize)
    if PROGRAM not in programs:
        return None
    flops, nbytes = programs[PROGRAM]
    return 100.0 * calls * spec.roofline_seconds(flops, nbytes, run.peak)[0] / busy
