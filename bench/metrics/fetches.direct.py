"""Blocking device→host reads per path of the host driver: Σ ``fetches``
over the spans of each window answer's path trace, averaged over paths.
None where an answer has no trace."""

import statistics

import path_traces


def read(run):
    traces = path_traces.window_traces(run)
    if traces is None:
        return None
    return statistics.fmean([path_traces.span_sum(t, "fetches")
                             for t in traces])
