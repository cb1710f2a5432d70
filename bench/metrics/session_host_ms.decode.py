"""Serving session: mean host time from the end of one wrapped
decode_step call to the start of the next (what ServeSession.step does
around the engine's step: sampling, the logits' wait, retirements, and
the harness's stamping of events), over consecutive pairs of calls that
lie in the window."""


def read(run):
    spans = run.decode.spans
    gaps = [start - end for (_, end), (start, stop) in zip(spans, spans[1:])
            if run.t_open <= end and stop <= run.t_close]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
