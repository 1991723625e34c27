"""Host-side spans of a run: the harness's own and JAX's compile pipeline.

``Spans`` records named intervals on the wall clock (``time.time_ns``) and,
when a profiler trace is being written, mirrors each into it as a
``TraceAnnotation``. ``CompileWatch`` listens to ``jax.monitoring``: the
duration events of tracing to a jaxpr, lowering to MLIR and the backend
compile step (which, on a persistent-cache hit, is the cache load), and the
cache's hit events. It gives the seconds spent in that pipeline (the union
of its intervals, since the events nest) and the number of real compiles.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Tuple

import jax
from jax import monitoring

from bench.trace_reduce import length, union

TRACE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"

#: Label of the compile pipeline's intervals among the host spans.
TRACE_LOWER = "trace/lower"


class Spans:
    def __init__(self):
        self.records: List[Tuple[str, int, int]] = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        start = time.time_ns()
        if self.annotate:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, start, time.time_ns()))


class CompileWatch:
    """Collects the compile pipeline's intervals and counts since ``mark``."""

    def __init__(self):
        self.intervals: List[Tuple[float, float]] = []
        self.backend_compiles = 0
        self.cache_hits = 0
        self._span_cb = self._on_span
        self._event_cb = self._on_event
        monitoring.register_event_time_span_listener(self._span_cb)
        monitoring.register_event_listener(self._event_cb)

    def _on_span(self, event, start_time, end_time, **kwargs):
        if event in TRACE_EVENTS:
            self.intervals.append((start_time, end_time))
            if event == BACKEND_COMPILE:
                self.backend_compiles += 1

    def _on_event(self, event, **kwargs):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def mark(self) -> Dict:
        return dict(n=len(self.intervals), compiles=self.backend_compiles,
                    hits=self.cache_hits)

    def since(self, mark: Dict) -> Dict:
        """Pipeline seconds and real compiles since ``mark``."""
        iv = self.intervals[mark["n"]:]
        return dict(trace_s=length(union(iv)),
                    compiles=(self.backend_compiles - mark["compiles"])
                    - (self.cache_hits - mark["hits"]))

    def spans_ns(self, mark: Dict) -> List[Tuple[str, int, int]]:
        return [(TRACE_LOWER, int(a * 1e9), int(b * 1e9))
                for a, b in self.intervals[mark["n"]:]]

    def close(self):
        monitoring.unregister_event_time_span_listener(self._span_cb)
        monitoring.unregister_event_listener(self._event_cb)
