"""Device time by the program's layer scopes, inside the program's own spans.

The program (``repro.obs``) runs each layer of the solve under a
``jax.named_scope`` (``claire.*``), so the JAX name path in the metadata of
every HLO instruction names its layer, and it records each solve as host
spans on the wall clock, mirrored into the profiler trace with their span
ids. This module reads both for one traced run:

- The window's solves are the last ``len(run.waves)`` records of
  ``repro.obs.recent``. The trace clock is aligned with theirs by the
  median, over every span of those records found in the trace (matched by
  span id), of trace start minus wall start, the rule of
  ``trace_reduce._clock_offset``.
- Each device op (``trace_reduce._device_ops``) takes its layer from the
  innermost ``claire.*`` scope of its instruction's ``op_name``, found
  through its program in the HLO stored with the trace. A fusion takes its
  own metadata; where that carries no scope, the scope path most of its
  fused instructions carry; an op with neither takes that of its nearest
  user (``_module_scopes``). Only op time inside the window's
  ``claire.solve`` spans counts; the harness's own data ops are left out.
- A scope's device time is the union of the intervals of the ops under it
  (at any depth), not their sum; ``layer_s`` gives each op to its
  innermost scope only. Times are per chip (summed over device planes,
  over their number).
- Device-idle time inside each solve is given to the program span open
  during it, innermost first; what no child span holds stays with
  ``claire.solve``.

With a program that records no spans (no ``repro.obs``) every reader gets
``None``. ``for_run`` reduces once per trace and prints its summary on
standard error.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import statistics
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce as tr
from bench import xspace

SOLVE = "claire.solve"
UNSCOPED = "(none)"
_SCOPE = re.compile(r"claire\.[A-Za-z0-9_.]*[A-Za-z0-9_]")

_memo: Dict[Tuple, Dict] = {}


def scopes_of(op_name: str) -> Tuple[str, ...]:
    """The ``claire.*`` scopes of a JAX name path, outermost first; a scope
    under a transform reads as ``vmap(claire.gradient)``."""
    return tuple(_SCOPE.findall(op_name))


# ---- what each instruction is ---------------------------------------------------

class OpScopes:
    """(scope path, opcode, custom-call target) of every instruction of every
    program the trace stores, by program id and instruction name."""

    def __init__(self, path: str):
        self.by_program: Dict[int, Dict[str, Tuple]] = {}
        self.module_names: Dict[int, str] = {}
        for pid, blob in xspace.hlo_protos(path).items():
            mod = hlo_module(blob)
            self.module_names[pid] = mod["name"]
            self.by_program[pid] = _module_scopes(mod)

    def get(self, program_id, name: str) -> Optional[Tuple]:
        return self.by_program.get(program_id, {}).get(name)


def hlo_module(hlo_proto: bytes) -> Dict:
    """``bench.xspace.hlo_module``'s reading of one ``HloProto``, with each
    instruction's ``id`` and ``operands`` (ids) as well."""
    module = next((v for n, _, v in xspace.fields(hlo_proto) if n == 1), b"")
    name, comps = "", {}
    for num, _, val in xspace.fields(module):
        if num == 1:
            name = val.decode()
        elif num == 3:
            cid, instrs = None, []
            for cn, _, cv in xspace.fields(val):
                if cn == 5:
                    cid = cv
                elif cn == 2:
                    ins = dict(name="", opcode="", op_name="", target="", id=None,
                               operands=[], calls=[])
                    for fn, fwt, fv in xspace.fields(cv):
                        if fn == 1:
                            ins["name"] = fv.decode()
                        elif fn == 2:
                            ins["opcode"] = fv.decode()
                        elif fn == 7:
                            ins["op_name"] = next((m.decode() for k, _, m in xspace.fields(fv)
                                                   if k == 2), "")
                        elif fn == 28:
                            ins["target"] = fv.decode()
                        elif fn == 35:
                            ins["id"] = fv
                        elif fn == 36:
                            ins["operands"].extend(xspace._packed_ints(fv, fwt))
                        elif fn == 38:
                            ins["calls"].extend(xspace._packed_ints(fv, fwt))
                    instrs.append(ins)
            comps[cid] = {"instructions": instrs}
    return {"name": name, "computations": comps}


def _module_scopes(mod: Dict) -> Dict[str, Tuple]:
    """Scope path of each instruction: its own; else, for a fusion or call,
    the path most of the instructions it calls carry; else that of its
    nearest user that has one. The TPU compiler gives the ops it adds
    around a gather (index clamps) the gather's own relative name, without
    the caller's path, and its buffer allocations for loop carries no name:
    they belong to the layer that consumes them."""
    comps = mod["computations"]
    memo: Dict[int, List[Tuple[str, ...]]] = {}

    def inner_paths(cid) -> List[Tuple[str, ...]]:
        if cid not in memo:
            memo[cid] = []
            out = []
            for ins in comps.get(cid, {"instructions": []})["instructions"]:
                path = scopes_of(ins["op_name"])
                if path:
                    out.append(path)
                elif ins["opcode"] not in tr.CONTAINERS:
                    for c in ins["calls"]:
                        out.extend(inner_paths(c))
            memo[cid] = out
        return memo[cid]

    out = {}
    for comp in comps.values():
        paths = {}
        for ins in comp["instructions"]:
            path = scopes_of(ins["op_name"])
            if not path and ins["opcode"] not in tr.CONTAINERS:
                fused = [p for c in ins["calls"] for p in inner_paths(c)]
                if fused:
                    path = collections.Counter(fused).most_common(1)[0][0]
            paths[ins["name"]] = path
        users = collections.defaultdict(list)
        by_id = {ins.get("id"): ins for ins in comp["instructions"]}
        for ins in comp["instructions"]:
            for o in ins.get("operands", ()):
                if o in by_id:
                    users[by_id[o]["name"]].append(ins)
        for ins in comp["instructions"]:
            path = paths[ins["name"]]
            if not path and ins["opcode"] not in tr.CONTAINERS:
                path = _user_path(ins["name"], users, paths)
            out[ins["name"]] = (path, ins["opcode"], ins["target"])
    return out


def _user_path(name: str, users, paths, depth: int = 8) -> Tuple[str, ...]:
    """Scope path of the nearest user (breadth first, ``depth`` levels at
    most) that has one; a loop or call that uses it ends the search (a
    buffer made for a loop's carry takes the loop's scope)."""
    seen, frontier = {name}, [name]
    for _ in range(depth):
        nxt = []
        for n in frontier:
            for u in users.get(n, ()):
                if u["name"] in seen:
                    continue
                if paths[u["name"]]:
                    return paths[u["name"]]
                seen.add(u["name"])
                if u["opcode"] not in tr.CONTAINERS:
                    nxt.append(u["name"])
        frontier = nxt
    return ()


# ---- the program's spans in the trace ---------------------------------------------

def _trace_spans(pd) -> Dict[int, Tuple[str, int, int]]:
    """Span id -> (name, start, end) on the trace clock, of every program
    span (``claire.*`` with a ``span_id`` stat) on the host planes."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith("claire."):
                    continue
                sid = dict(ev.stats).get("span_id")
                if sid is not None:
                    a = int(ev.start_ns)
                    out[int(sid)] = (ev.name, a, a + int(ev.duration_ns))
    return out


def clock_offset(pd, records: Sequence[Dict]) -> Tuple[int, int, int]:
    """(offset, largest distance of one span's offset from it, spans
    matched): trace clock minus wall clock, the median over the records'
    spans found in the trace."""
    found = _trace_spans(pd)
    diffs = [found[s["id"]][1] - s["start_ns"]
             for r in records for s in r["spans"] if s["id"] in found]
    if not diffs:
        raise ValueError("no span of the program's records is in the trace")
    off = int(statistics.median(diffs))
    return off, max(abs(d - off) for d in diffs), len(diffs)


def _depth(span: Dict, by_id: Dict[int, Dict]) -> int:
    d = 0
    while span["parent"] in by_id:
        span = by_id[span["parent"]]
        d += 1
    return d


def _idle_by_span(gaps, records, offset) -> Dict[str, float]:
    """Give idle stretches to the program span open during them, deepest
    first; seconds by span name."""
    out: Dict[str, float] = collections.defaultdict(float)
    for rec in records:
        by_id = {s["id"]: s for s in rec["spans"]}
        order = sorted(rec["spans"], key=lambda s: -_depth(s, by_id))
        for s in order:
            part = tr.intersect(gaps, [(s["start_ns"] + offset, s["end_ns"] + offset)])
            if part:
                out[s["name"]] += tr.length(part) / 1e9
                gaps = tr.subtract(gaps, part)
    return dict(out)


# ---- the reduction ------------------------------------------------------------------

def reduce(path: str, records: Sequence[Dict], host_spans=None, top: int = 10) -> Dict:
    """Scope times of the ops inside the records' solve spans (see the
    module's docstring). With the harness's ``host_spans`` (wall-clock
    ``(name, start, end)``, ``trace/lower`` included) it also gives the
    harness's clock offset and splits the idle time the harness gives to
    its ``solve`` span by program span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops_info = OpScopes(path)
    offset, offset_dev, matched = clock_offset(pd, records)
    solves = tr.union([(r["start_ns"] + offset, r["end_ns"] + offset) for r in records])

    per_plane: Dict[str, Dict[str, List]] = {}
    per_op: Dict[str, List] = {}
    op_ns = 0
    for plane, name, pid, a, b in tr._device_ops(pd):
        parts = tr.intersect([(a, b)], solves)
        if not parts:
            continue
        info = ops_info.get(pid, name)
        module = ops_info.module_names.get(pid, f"program {pid}")
        if tr.HARNESS_MODULE in module or (info and info[1] in tr.CONTAINERS):
            continue
        path_, opcode, target = info if info else ((), "?", "")
        iv = per_plane.setdefault(plane, collections.defaultdict(list))
        layer = path_[-1] if path_ else UNSCOPED
        for p in parts:
            iv["*"].append(p)
            iv["layer:" + layer].append(p)
            for sc in set(path_):
                iv[sc].append(p)
        ns = sum(q - p for p, q in parts)
        op_ns += ns
        rec = per_op.setdefault(f"{module}/{name}", [0, 0, layer, opcode, target])
        rec[0] += ns
        rec[1] += 1
    if not per_plane:
        raise ValueError("no device op ran inside the program's solve spans")
    n_dev = len(per_plane)
    unions = collections.defaultdict(float)
    for iv in per_plane.values():
        for key, lst in iv.items():
            unions[key] += tr.length(tr.union(lst)) / 1e9 / n_dev

    first = per_plane[sorted(per_plane)[0]]
    busy = tr.union(first["*"])
    gaps = tr.subtract(solves, busy)
    out = dict(
        offset_ns=offset, offset_spread_ns=offset_dev, spans_matched=matched,
        solve_s=tr.length(solves) / 1e9,
        busy_s=unions["*"],
        op_s=op_ns / 1e9 / n_dev,
        overlap_s=op_ns / 1e9 / n_dev - unions["*"],
        scope_s={k: v for k, v in unions.items() if k.startswith("claire.")},
        layer_s={k[6:]: v for k, v in unions.items() if k.startswith("layer:")},
        idle_s=_idle_by_span(gaps, records, offset),
        top_ops=[[k, v[0] / 1e9 / n_dev, v[1], v[2], v[3], v[4]]
                 for k, v in sorted(per_op.items(), key=lambda kv: -kv[1][0])[:top]],
    )
    out["unscoped_s"] = out["layer_s"].get(UNSCOPED, 0.0)
    if host_spans is not None:
        h_off = tr._clock_offset(pd, host_spans)
        # The harness's labelling of idle time (trace_reduce.SPAN_ORDER,
        # innermost first); its last label, solve, is split by program span.
        def label(name):
            return tr.union([(a + h_off, b + h_off) for n, a, b in host_spans
                             if n == name])

        *first_labels, solve = tr.SPAN_ORDER
        rest = gaps
        for name in first_labels:
            rest = tr.subtract(rest, label(name))
        out.update(harness_offset_ns=h_off,
                   solve_gap_s=_idle_by_span(tr.intersect(rest, label(solve)),
                                             records, offset))
    return out


def window_records(run) -> Optional[List[Dict]]:
    """The solve records of the run's window, or None where the program
    records none."""
    try:
        from repro import obs
    except ImportError:
        return None
    recs = [r for r in obs.recent(len(run.waves)) if r["name"] == SOLVE]
    return recs if recs and len(recs) == len(run.waves) else None


def newest_trace() -> Optional[str]:
    from bench.harness import TRACE_DIR

    found = sorted(glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


def for_run(run) -> Optional[Dict]:
    """The reduction of the run's trace, once per trace; None without a
    trace or without the program's records."""
    if run.trace is None:
        return None
    recs = window_records(run)
    path = newest_trace()
    if recs is None or path is None:
        return None
    key = (path, os.path.getmtime(path), tuple(r["id"] for r in recs))
    if key not in _memo:
        t0 = time.perf_counter()
        out = reduce(path, recs)
        out["reduce_s"] = time.perf_counter() - t0
        _memo[key] = out
        log(out, run.trace["busy_s"])
    return _memo[key]


def log(out: Dict, busy_s: float, file=sys.stderr) -> None:
    def p(*a):
        print("scopes", *a, file=file)

    p(f"offset_ns {out['offset_ns']} spread_ns {out['offset_spread_ns']} "
      f"spans {out['spans_matched']} reduce_s {out.get('reduce_s')!r}")
    p(f"in_solves busy_s {out['busy_s']!r} op_s {out['op_s']!r} "
      f"overlap_s {out['overlap_s']!r} unscoped_s {out['unscoped_s']!r} "
      f"unscoped_share_of_busy {100.0 * out['unscoped_s'] / busy_s!r} %")
    for k, v in sorted(out["scope_s"].items()):
        p(f"scope {k} {v!r} s")
    for k, v in sorted(out["layer_s"].items()):
        p(f"layer {k} {v!r} s")
    for k, v in sorted(out["idle_s"].items(), key=lambda kv: -kv[1]):
        p(f"idle {k} {v!r} s")
    for k, v in sorted(out.get("solve_gap_s", {}).items(), key=lambda kv: -kv[1]):
        p(f"solve_gap {k} {v!r} s")
    for name, s, n, layer, opcode, target in out["top_ops"]:
        p(f"op {name} {s!r} s x{n} layer={layer} opcode={opcode} target={target}")
