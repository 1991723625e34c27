"""Device time of FFT ops (spectral regularizer and preconditioner) over
device busy time."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * run.trace["category_s"]["fft"] / run.trace["busy_s"]
