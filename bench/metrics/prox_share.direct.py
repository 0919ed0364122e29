"""Device time of the sorted-L1 prox kernel over device-busy time, from
the profiler trace of the window (``trace_reduce``).  None where the trace
holds no prox kernel."""


def read(run):
    t = run["trace"]
    if not t["prox_s"] or not t["busy_s"]:
        return None
    return 100.0 * t["prox_s"] / t["busy_s"]
