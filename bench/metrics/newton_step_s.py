"""Mean seconds of one Newton step evaluation that traced nothing: the
``claire.newton`` spans of the window's solve records (``repro.obs``) whose
``traces.*`` counters are all 0. A batched step serves all pairs of its
wave."""

from bench import scopes


def read(run):
    recs = scopes.window_records(run)
    if recs is None:
        return None
    steps = [s["end_ns"] - s["start_ns"] for r in recs for s in r["spans"]
             if s["name"] == "claire.newton"
             and not any(k.startswith("traces.") and v for k, v in s["counters"].items())]
    return sum(steps) / len(steps) / 1e9 if steps else None
