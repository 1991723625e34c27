"""Device milliseconds per Hessian matvec: device time under the
``claire.matvec`` scope inside the window's solve spans (``bench.scopes``:
the union of those ops' intervals), over the matvecs of all pairs
completed."""

from bench import scopes


def read(run):
    out = scopes.for_run(run)
    matvecs = sum(p["matvecs"] for p in run.pairs)
    if out is None or matvecs <= 0 or "claire.matvec" not in out["scope_s"]:
        return None
    return 1e3 * out["scope_s"]["claire.matvec"] / matvecs
