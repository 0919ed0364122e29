"""Host→device megabytes per path of the host driver: Σ ``h2d_bytes``
over the spans of each window answer's path trace, over 1e6, averaged
over paths.  None where an answer has no trace."""

import statistics

import path_traces


def read(run):
    traces = path_traces.window_traces(run)
    if traces is None:
        return None
    return statistics.fmean([path_traces.span_sum(t, "h2d_bytes") / 1e6
                             for t in traces])
