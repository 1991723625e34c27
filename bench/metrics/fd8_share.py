"""Device time under the ``claire.fd8`` scope (FD8 first derivatives)
inside the window's solve spans (``bench.scopes``), over device busy time,
as ``interp_share``."""

from bench import scopes


def read(run):
    out = scopes.for_run(run)
    if out is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * out["scope_s"].get("claire.fd8", 0.0) / run.trace["busy_s"]
