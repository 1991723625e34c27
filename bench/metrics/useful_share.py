"""Share of the pair-steps a batched solve computed that belonged to pairs
still active: each wave evaluates all of its pairs at every Newton step
until the slowest converges. Nothing to read for one pair per solve."""


def read(run):
    if run.batch == 1:
        return None
    done = sum(p["evals"] for p in run.pairs)
    return 100.0 * sum(p["active_evals"] for p in run.pairs) / done
