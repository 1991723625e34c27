"""Mean Hessian matvecs (PCG iterations, final step included) per pair,
from the solver's result."""


def read(run):
    return sum(p["matvecs"] for p in run.pairs) / len(run.pairs)
