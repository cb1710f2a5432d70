"""I/O: the decode steps' measured fetch wait (StepStats.io_wait_seconds)
over their wall time, both over the window."""


def read(run):
    wall = sum(run.decode.within(run.t_open, run.t_close))
    return 100.0 * run.io_wait_s / wall if wall else None
