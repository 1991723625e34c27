"""Seconds per pair of post-solve scoring (warped image, mismatch, det F):
the ``claire.score`` spans of the window's solve records (``repro.obs``),
summed, over pairs completed."""

from bench import scopes


def read(run):
    recs = scopes.window_records(run)
    if recs is None:
        return None
    return sum(s["end_ns"] - s["start_ns"] for r in recs for s in r["spans"]
               if s["name"] == "claire.score") / 1e9 / run.n_pairs
