"""FISTA iterations per CV (all folds, all steps), averaged over CVs."""


def read(run):
    its = [a.info["iters"] for a in run["answers"] if a.error is None]
    return sum(its) / len(its) if its else None
