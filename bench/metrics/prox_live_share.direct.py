"""Share of the pool prox's serial loops that ran: 100 × Σ ``prox_live``
/ Σ ``prox_full`` over the ``solve`` spans of every window answer's path
trace.  ``prox_live`` sums the live bound of each prox call's input (the
loops stop there), ``prox_full`` its width.  None where an answer has no
trace or the spans carry no such count."""

import path_traces


def read(run):
    traces = path_traces.window_traces(run)
    if traces is None:
        return None
    full = sum(path_traces.span_sum(t, "prox_full") for t in traces)
    if not full:
        return None
    live = sum(path_traces.span_sum(t, "prox_live") for t in traces)
    return 100.0 * live / full
