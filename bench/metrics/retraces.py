"""Traces of the program's jitted functions per pair in the window: the
``traces.*`` counters of the window's solve records (``repro.obs``),
summed, over pairs completed. A trace that the in-memory cache would have
served is a retrace the chip waits for."""

from bench import scopes


def read(run):
    recs = scopes.window_records(run)
    if recs is None:
        return None
    return sum(v for r in recs for k, v in r["counters"].items()
               if k.startswith("traces.")) / run.n_pairs
