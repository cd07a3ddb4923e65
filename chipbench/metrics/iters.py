"""Mean iterations per PageRank solve (``SolveResult.iterations``)."""


def read(name, run):
    return run.window.get("iters_mean")
