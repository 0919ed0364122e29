"""The host path driver's traces of a run's window answers, for the
readers of ``h2d_mb.direct``, ``fetches.direct`` and
``driver_share.direct``.

The program keeps each finished host path trace in a bounded recorder
(``repro.obs.trace.recent``).  An answer's trace is the one whose span
lies within the answer's ``[t0, t1]``: both are ``time.perf_counter``
times of one process, and the warm pass's paths end before the window
opens.
"""


def window_traces(run):
    """One trace per answer of the window, in answer order; None where
    the program keeps no path traces or an answer has not exactly one."""
    try:
        from repro.obs.trace import recent
    except ImportError:  # a program without the recorder
        return None
    kept = recent()
    out = []
    for a in run["answers"]:
        hit = [t for t in kept if a.t0 <= t.t0 and t.cursor <= a.t1]
        if len(hit) != 1:
            return None
        out.append(hit[0])
    return out or None


def span_sum(trace, key):
    """Σ of attribute ``key`` over the trace's top-level spans."""
    return sum(s.attrs.get(key, 0) for s in trace.top())
