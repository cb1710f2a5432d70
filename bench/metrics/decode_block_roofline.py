"""Model and kernels: the least time the calls of the jitted decode
programs in the trace could take, over their summed device time.  The
configuration's family names the programs its layers run and the
operations and bytes of one call of each; a call's least time is its
bytes over HBM bandwidth or its operations over peak, whichever is larger
(for the dense family at these shapes, bytes bound it)."""

from bench import spec


def read(run):
    if run.trace is None:
        return None
    eng, cfg, slots = run.engine, run.config, run.traffic["slots"]
    context = eng["n_select"] * eng["group_size"] + eng["group_size"]
    itemsize = 4 if cfg["torch_dtype"] == "float32" else 2
    programs = spec.family(cfg["family"]).decode_programs(cfg, slots, context, itemsize)
    calls, device_s = run.trace["program_calls"], run.trace["program_s"]
    busy = sum(device_s.get(p, 0.0) for p in programs)
    if busy <= 0.0:
        return None
    least = sum(100.0 * calls.get(p, 0) * spec.roofline_seconds(f, b, run.peak)[0]
                for p, (f, b) in programs.items())
    return least / busy
