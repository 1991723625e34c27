"""Device time of the ops that gather interpolation taps, over device busy
time (the program's ops only; the harness's own data ops count as busy)."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * run.trace["category_s"]["gather"] / run.trace["busy_s"]
