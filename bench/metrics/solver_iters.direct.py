"""FISTA iterations per path, summed over its steps, averaged over paths."""


def read(run):
    its = [a.info["iters"] for a in run["answers"] if a.error is None]
    return sum(its) / len(its) if its else None
