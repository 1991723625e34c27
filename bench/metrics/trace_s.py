"""Seconds per pair that JAX spent tracing, lowering, and compiling or
loading from the persistent cache inside the window (union of the
``jax.monitoring`` duration events' intervals, over pairs completed)."""


def read(run):
    return run.pipeline["trace_s"] / run.n_pairs
