"""From a profiler trace to the numbers the per-layer metrics read.

- Device ops are the events of the ``XLA Ops`` line of each ``/device:TPU:``
  plane. Control-flow containers (``while``, ``conditional``, ``call``)
  are left out: their bodies' ops are events of their own.
- Each op is sorted into ``gather``, ``fft`` or ``other`` by its HLO opcode
  and the JAX name path of its metadata (``_tags``; ``harness`` for the
  benchmark's own data ops), found through its program in the HLO module
  that the profiler stores with the trace
  (``bench.xspace``); an op not found there is an error. A fusion counts
  as ``fft`` if it contains an ``fft``, else ``gather`` if it contains a
  ``gather``.
- Busy time is the union of the op intervals inside the window; idle share
  is one minus busy over the window.
- Each idle stretch is given to the host span open during it, innermost
  first: ``trace/lower`` (JAX tracing, lowering, compiling or loading from
  the persistent cache), then the harness's ``data`` and ``solve``; what is
  left is ``host``.
"""

from __future__ import annotations

import bisect
import json
import pathlib
import statistics
from typing import Dict, List, Sequence, Tuple

from bench import xspace

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
CONTAINERS = {"while", "conditional", "call", "async-start", "async-done"}
#: Host spans, innermost first.
SPAN_ORDER = ("trace/lower", "data", "solve")
HARNESS_SPANS = ("data", "solve")
#: The harness's own device work (``bench.data.MATERIALIZE``): busy time,
#: but no layer of the program.
HARNESS_MODULE = "bench_materialize"

Interval = Tuple[float, float]


def peaks(device_kind: str) -> Dict:
    """Published peaks of one chip; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


# ---- interval arithmetic on sorted, disjoint lists -----------------------------

def union(iv: Sequence[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def intersect(x: List[Interval], y: List[Interval]) -> List[Interval]:
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if a < b:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(x: List[Interval], y: List[Interval]) -> List[Interval]:
    out = []
    j = 0
    for a, b in x:
        cur = a
        while j < len(y) and y[j][1] <= cur:
            j += 1
        k = j
        while k < len(y) and y[k][0] < b:
            if y[k][0] > cur:
                out.append((cur, y[k][0]))
            cur = max(cur, y[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def length(x: List[Interval]) -> float:
    return sum(b - a for a, b in x)


# ---- op classification ----------------------------------------------------------

class OpClasses:
    """Category of each device op, by its program id and the HLO module the
    profiler stored for that program. An op whose program or instruction is
    not there is an error: nothing is sorted by a guess."""

    def __init__(self, path: str):
        self.by_program: Dict[int, Dict[str, str]] = {}
        self.module_names: Dict[int, str] = {}
        for pid, blob in xspace.hlo_protos(path).items():
            mod = xspace.hlo_module(blob)
            self.module_names[pid] = mod["name"]
            self.by_program[pid] = _classify_module(mod)

    def category(self, program_id, op_name: str) -> str:
        if program_id not in self.by_program:
            raise KeyError(f"device op {op_name!r}: program id {program_id!r} "
                           "has no HLO module in the trace")
        if HARNESS_MODULE in self.module_names[program_id]:
            return "harness"
        cats = self.by_program[program_id]
        if op_name not in cats:
            raise KeyError(f"device op {op_name!r} is no instruction of "
                           f"{self.module_names[program_id]!r}")
        return cats[op_name]


def _tags(ins: Dict) -> set:
    """What one instruction does: its opcode, plus ``fft`` or ``gather``
    where the JAX name path of its metadata says so. A TPU program has no
    ``fft`` opcode: XLA expands the FFT into DFT convolutions, which keep
    the ``fft`` primitive (``jit(fft)``) in their name path; a gather can
    come with a ``custom-call`` that asserts its indices in bounds."""
    out = {ins["opcode"]}
    path = {c[4:-1] if c.startswith("jit(") and c.endswith(")") else c
            for c in ins["op_name"].split("/")}
    if "fft" in path:
        out.add("fft")
    if ins["opcode"] == "custom-call" and "Gather" in ins["target"]:
        out.add("gather")
    return out


def _classify_module(mod: Dict) -> Dict[str, str]:
    comps = mod["computations"]
    memo: Dict[int, set] = {}

    def opcodes(cid) -> set:
        if cid in memo:
            return memo[cid]
        memo[cid] = set()
        out = set()
        for ins in comps.get(cid, {"instructions": []})["instructions"]:
            out |= _tags(ins)
            if ins["opcode"] not in CONTAINERS:
                for c in ins["calls"]:
                    out |= opcodes(c)
        memo[cid] = out
        return out

    cats = {}
    for comp in comps.values():
        for ins in comp["instructions"]:
            if ins["opcode"] in CONTAINERS:
                cats[ins["name"]] = "container"
                continue
            ops = _tags(ins)
            for c in ins["calls"]:
                ops |= opcodes(c)
            cats[ins["name"]] = ("fft" if "fft" in ops else
                                 "gather" if "gather" in ops else "other")
    return cats


# ---- the reduction ----------------------------------------------------------------

def _instruction(name: str) -> str:
    """Instruction name of an op event: a TPU trace names the op by its HLO
    text (``%copy.1 = s32[4]{0} copy(...)``), a CPU trace by the name."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def _program_id(stats) -> object:
    pid = dict(stats).get("program_id")
    return None if pid is None else int(pid)


def _module_id(name: str, stats) -> object:
    """Program id of an ``XLA Modules`` event: its ``program_id`` stat, or
    the number a TPU trace puts after the module's name (``jit_f(123)``)."""
    pid = _program_id(stats)
    if pid is None and name.endswith(")") and "(" in name:
        tail = name[name.rindex("(") + 1:-1]
        pid = int(tail) if tail.isdigit() else None
    return pid


def _device_ops(pd) -> List[Tuple[str, str, object, int, int]]:
    """(plane, instruction name, program id, start ns, end ns) of every
    device op. An op without a ``program_id`` stat of its own takes that of
    the ``XLA Modules`` event it runs in on the same plane."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:") or "SparseCore" in plane.name:
            continue
        lines = {line.name: line for line in plane.lines}
        modules = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                          _module_id(ev.name, ev.stats))
                         for ev in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else []))
        starts = [m[0] for m in modules]
        if "XLA Ops" not in lines:
            continue
        for ev in lines["XLA Ops"].events:
            a, b = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
            pid = _program_id(ev.stats)
            if pid is None:
                k = bisect.bisect_right(starts, a) - 1
                if k >= 0 and a < modules[k][1]:
                    pid = modules[k][2]
            out.append((plane.name, _instruction(ev.name), pid, a, b))
    return out


def _clock_offset(pd, host_spans) -> int:
    """Trace clock minus wall clock, from the harness spans, which are both
    recorded on the wall clock and annotated in the trace."""
    found: Dict[str, List[int]] = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in HARNESS_SPANS:
                    found.setdefault(ev.name, []).append(int(ev.start_ns))
    diffs = []
    for name in HARNESS_SPANS:
        wall = [s for n, s, _ in host_spans if n == name]
        tr = sorted(found.get(name, []))
        for a, b in zip(sorted(wall), tr):
            diffs.append(b - a)
    if not diffs:
        raise ValueError("no harness span found in the trace to align the clocks")
    return int(statistics.median(diffs))


def reduce(path: str, host_spans, window_wall_ns: Interval, device_kind: str,
           top: int = 10) -> Dict:
    from jax.profiler import ProfileData

    chip = peaks(device_kind)
    pd = ProfileData.from_file(path)
    classes = OpClasses(path)
    offset = _clock_offset(pd, host_spans)
    win = (window_wall_ns[0] + offset, window_wall_ns[1] + offset)
    cat_ns = {"gather": 0, "fft": 0, "other": 0, "harness": 0}
    per_op: Dict[str, List] = {}
    intervals: Dict[str, List[Interval]] = {}
    for plane, name, pid, a, b in _device_ops(pd):
        a, b = max(a, win[0]), min(b, win[1])
        if a >= b:
            continue
        cat = classes.category(pid, name)
        if cat == "container":
            continue
        intervals.setdefault(plane, []).append((a, b))
        cat_ns[cat] += b - a
        key = f"{classes.module_names[pid]}/{name} [{cat}]"
        rec = per_op.setdefault(key, [0, 0])
        rec[0] += b - a
        rec[1] += 1
    if not intervals:
        raise ValueError("no device op ran in the traced window")
    n_dev = len(intervals)
    busy = {p: union(iv) for p, iv in intervals.items()}
    window_ns = win[1] - win[0]
    busy_ns = sum(length(iv) for iv in busy.values()) / n_dev

    # Idle stretches of the first chip, by the host span open during them.
    gaps = subtract([win], busy[sorted(busy)[0]])
    by_label: Dict[str, float] = {}
    for label in SPAN_ORDER:
        iv = union([(a + offset, b + offset) for n, a, b in host_spans if n == label])
        part = intersect(gaps, iv)
        by_label[label] = length(part) / 1e9
        gaps = subtract(gaps, part)
    by_label["host"] = length(gaps) / 1e9

    ops_sorted = sorted(per_op.items(), key=lambda kv: -kv[1][0])
    return dict(
        busy_s=busy_ns / 1e9,
        window_s=window_ns / 1e9,
        category_s={k: v / 1e9 / n_dev for k, v in cat_ns.items()},
        gap_s=by_label,
        n_ops=sum(v[1] for v in per_op.values()),
        peaks=chip,
        breakdown=dict(
            device_ops=[[k, v[0] / 1e9 / n_dev] for k, v in ops_sorted[:top]],
            idle_gaps=sorted([[k, v] for k, v in by_label.items() if v > 0],
                             key=lambda kv: -kv[1])[:top]),
    )
