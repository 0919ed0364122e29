"""Share of a path spent outside FISTA sub-solves: 100 × (path span total
− Σ ``solve`` spans) / path span total, from each window answer's path
trace, averaged over paths.  Read beside ``idle_share.direct``.  None
where an answer has no trace."""

import statistics

import path_traces


def read(run):
    traces = path_traces.window_traces(run)
    if traces is None:
        return None
    shares = []
    for t in traces:
        solve = sum(s.duration_s for s in t.top() if s.name == "solve")
        shares.append(100.0 * (t.total_s - solve) / t.total_s)
    return statistics.fmean(shares)
