"""Mean accepted Newton iterations per pair, from the solver's result."""


def read(run):
    return sum(p["iters"] for p in run.pairs) / len(run.pairs)
