"""Engine step: mean wall time of decode_step calls that ended in the
window, each timed until its logits were ready."""


def read(run):
    xs = run.decode.within(run.t_open, run.t_close)
    return 1e3 * sum(xs) / len(xs) if xs else None
