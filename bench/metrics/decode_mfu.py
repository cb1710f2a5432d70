"""Whole step: tokens emitted in the traced window times the model's
operations per decoded token (every layer attending its M*G selected
tokens plus half a group of tail, then the head; counted by the
configuration's family) over the window and the chip's bf16 peak."""

from bench import spec
from bench.stats import tokens_in


def read(run):
    if run.trace is None or run.trace_bounds is None:
        return None
    t0, t1 = run.trace_bounds
    tokens = tokens_in(run.requests, t0, t1)
    eng = run.engine
    attended = eng["n_select"] * eng["group_size"] + eng["group_size"] // 2
    family = spec.family(run.config["family"])
    flops = tokens * family.decode_token_flops(run.config, attended)
    return 100.0 * flops / (t1 - t0) / run.peak["bf16_flops_per_s"]
