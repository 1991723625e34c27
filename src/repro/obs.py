"""Spans and counters of the solve path.

Three kinds of record, all always on:

- **Layer scopes** (device ops). Each layer of the solve runs inside
  ``jax.named_scope(<scope>)``, so the JAX name path in the metadata of
  every HLO instruction it lowers to names its layer (the ``op_name`` a
  profiler trace stores with the program). Scopes nest, e.g.
  ``.../claire.pcg/.../claire.matvec/.../claire.interp.apply/...``. They
  cost nothing at run time: they exist only while JAX traces.
- **Host spans** (``span``). Each records its name, start and end on
  ``time.time_ns()``, a span id, its parent's id and the id of the solve it
  belongs to, and mirrors itself into the profiler as a
  ``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` when it has a
  ``step_num``) carrying ``span_id`` and ``solve_id``, so a trace can be
  aligned with these records span by span.
- **Trace counters** (``count_trace``). Called in the body of a jitted
  program, so it runs only when JAX traces that program. It adds 1 to
  ``traces.<program>`` on every span open on the calling thread: a span's
  counters hold those of the spans inside it.

Each closed ``claire.solve`` span becomes one record: its span tree and its
counters. Records go into a bounded buffer; ``recent(n)`` returns the last
``n``. Spans opened outside a ``claire.solve`` reach the profiler only.
Span stacks are per thread (the serving path solves on its own thread).
``claire.score`` names both a host span (the scoring call) and the layer
scope of its device ops.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import threading
import time
from typing import Dict, List, Optional

import jax

# ---- layer scopes (device ops) ----------------------------------------------

GRADIENT = "claire.gradient"
PCG = "claire.pcg"
MATVEC = "claire.matvec"
PRECOND = "claire.precond"
LINESEARCH = "claire.linesearch"
INTERP_PLAN = "claire.interp.plan"
INTERP_PREFILTER = "claire.interp.prefilter"
INTERP_APPLY = "claire.interp.apply"
FD8 = "claire.fd8"
SPECTRAL = "claire.spectral"
SCORE = "claire.score"

SCOPES = (GRADIENT, PCG, MATVEC, PRECOND, LINESEARCH, INTERP_PLAN,
          INTERP_PREFILTER, INTERP_APPLY, FD8, SPECTRAL, SCORE)

# ---- host spans -----------------------------------------------------------------

SOLVE = "claire.solve"
BUILD = "claire.build"
LEVEL = "claire.level"
NEWTON = "claire.newton"
DISPATCH = "claire.newton.dispatch"
SYNC = "claire.newton.sync"
DICE = "claire.dice"

#: Records kept: a few hundred solves.
MAX_RECORDS = 256


def scoped(name: str):
    """Decorator: run the function inside the layer scope ``name``."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


class Span:
    """One host span. ``end_ns`` is None while it is open."""

    __slots__ = ("id", "parent", "solve_id", "name", "start_ns", "end_ns",
                 "attrs", "counters")

    def __init__(self, id_, parent, solve_id, name, attrs):
        self.id, self.parent, self.solve_id = id_, parent, solve_id
        self.name, self.attrs = name, attrs
        self.start_ns = time.time_ns()
        self.end_ns: Optional[int] = None
        self.counters: Dict[str, int] = {}

    def as_dict(self) -> Dict:
        return dict(id=self.id, parent=self.parent, solve_id=self.solve_id,
                    name=self.name, start_ns=self.start_ns, end_ns=self.end_ns,
                    attrs=dict(self.attrs), counters=dict(self.counters))


_ids = itertools.count(1)
_local = threading.local()
_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_lock = threading.Lock()


def _state():
    if not hasattr(_local, "stack"):
        _local.stack, _local.closed = [], []
    return _local


@contextlib.contextmanager
def span(name: str, **attrs):
    """A host span, child of the span open on this thread. ``step_num``
    among ``attrs`` marks a step for the profiler. Yields the ``Span``."""
    st = _state()
    parent = st.stack[-1] if st.stack else None
    sid = next(_ids)
    s = Span(sid, parent.id if parent else None,
             parent.solve_id if parent else sid, name, attrs)
    if parent is None:
        st.closed = []
    st.stack.append(s)
    marks = {"step_num": attrs["step_num"]} if "step_num" in attrs else {}
    ann = jax.profiler.StepTraceAnnotation if marks else jax.profiler.TraceAnnotation
    try:
        with ann(name, span_id=sid, solve_id=s.solve_id, **marks):
            yield s
    finally:
        s.end_ns = time.time_ns()
        st.stack.pop()
        st.closed.append(s)
        if parent is None and name == SOLVE:
            rec = s.as_dict()
            rec["spans"] = [c.as_dict() for c in sorted(st.closed, key=lambda c: c.id)]
            with _lock:
                _records.append(rec)


def count_trace(program: str) -> None:
    """Count one trace of ``program`` on every span open on this thread.
    Call it in the body of the jitted function: it runs only when JAX
    traces it."""
    key = f"traces.{program}"
    for s in _state().stack:
        s.counters[key] = s.counters.get(key, 0) + 1


def elapsed_s(first: Optional[Span], last: Optional[Span]) -> float:
    """Wall seconds from the start of ``first`` to the end of ``last``."""
    if first is None or last is None:
        return 0.0
    return ((last.end_ns or time.time_ns()) - first.start_ns) / 1e9


def recent(n: int = MAX_RECORDS) -> List[Dict]:
    """The last ``n`` solve records, oldest first. Each is the
    ``claire.solve`` span as a dict (``id``, ``parent``, ``solve_id``,
    ``name``, ``start_ns``, ``end_ns``, ``attrs``, ``counters``) plus
    ``spans``: every span of the solve, itself included, in opening order."""
    with _lock:
        recs = list(_records)
    return recs[-n:] if n > 0 else []
