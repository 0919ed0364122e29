"""Share of fold steps that the compact engine sent to its masked
fallback (``BatchedPathResult.compact_fallback``), over all fold steps."""


def read(run):
    done = [a for a in run["answers"] if a.error is None]
    steps = sum(a.info["steps"] for a in done)
    return 100.0 * sum(a.info["fallback"] for a in done) / steps \
        if steps else None
