"""Mean screened set over p: the strong rule's kept predictors per path
step (``PathResult.steps[i].n_screened``), averaged over steps and paths."""


def read(run):
    shares = [sum(a.info["screened"][1:]) / (len(a.info["screened"]) - 1)
              / a.info["p"] for a in run["answers"]
              if a.error is None and len(a.info.get("screened", ())) > 1]
    return 100.0 * sum(shares) / len(shares) if shares else None
