"""Least time of the scalar interpolations the algorithm needs, over the
device time of the gather ops (``interp_share``'s numerator).

Counts come from the solver's own per-step records and the code's
structure, per pair (nt time steps):

- each Newton step evaluation: footpoints forward and backward (3 + 3
  interpolations of the velocity's components), the state solve (nt) and
  the adjoint solve (2 nt: the adjoint and its source);
- each PCG matvec: incremental state and incremental adjoint, 2 nt each;
- each line-search trial: footpoints (3) and a state solve (nt);
- scoring the result once: the warped image (3 + nt) and det F's composed
  displacement (3 + 3 nt).

Each scalar interpolation at N^3 points moves at least 20 bytes per point
in float32: the source value, three footpoint coordinates and the output.
The bound is the HBM bandwidth of ``bench/peaks.json``; no float32 vector
peak is published, so no compute bound is used. Only the steps of pairs
still active count, so a batch's masked work lowers the share.
"""


def interpolations(p, nt):
    per_eval = 6 + 3 * nt
    total = sum(per_eval + 4 * nt * pcg + (3 + nt) * ls
                for pcg, ls in zip(p["pcg"], p["ls"]))
    return total + (6 + 4 * nt)


def read(run):
    if run.trace is None or run.trace["category_s"]["gather"] <= 0:
        return None
    nt = int(run.solver["nt"])
    points = run.grid ** 3
    count = sum(interpolations(p, nt) for p in run.pairs)
    least_s = count * points * 20.0 / run.trace["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / run.trace["category_s"]["gather"]
