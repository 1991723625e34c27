"""Minimal protobuf wire reader for the profiler's ``.xplane.pb`` files.

It reads what ``jax.profiler.ProfileData`` does not expose: the HLO modules
that the profiler stores in the ``/host:metadata`` plane (``hlo_proto``
stats of its event metadata), so the trace reduction can look inside
fusions. Field numbers follow ``tsl/profiler/protobuf/xplane.proto`` and
``xla/service/hlo.proto``.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; length-delimited
    values are bytes, varints ints, fixed-width values raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt == 1:
            val = buf[i:i + 8]
            i += 8
        elif wt == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield num, wt, val


def _packed_ints(val, wt) -> List[int]:
    if wt == 0:
        return [val]
    out, i = [], 0
    while i < len(val):
        x, i = _varint(val, i)
        out.append(x)
    return out


def hlo_protos(path: str) -> Dict[int, bytes]:
    """Serialized ``HloProto`` messages stored in the trace's metadata plane,
    by program id (the ``program_id`` stat of the device's op events)."""
    with open(path, "rb") as fh:
        space = fh.read()
    out: Dict[int, bytes] = {}
    for num, _, plane in fields(space):
        if num != 1:
            continue
        stat_names: Dict[int, str] = {}
        metas = []
        for pn, _, pv in fields(plane):
            if pn == 5:  # stat_metadata map entry
                for kn, _, kv in fields(pv):
                    if kn == 2:
                        sid, sname = None, ""
                        for sn, _, sv in fields(kv):
                            if sn == 1:
                                sid = sv
                            elif sn == 2:
                                sname = sv.decode()
                        stat_names[sid] = sname
            elif pn == 4:  # event_metadata map entry
                for kn, _, kv in fields(pv):
                    if kn == 2:
                        metas.append(kv)
        for meta in metas:
            program_id = None
            for mn, _, mv in fields(meta):
                if mn == 1:
                    program_id = mv
                if mn != 5:
                    continue
                sid, blob = None, None
                for sn, _, sv in fields(mv):
                    if sn == 1:
                        sid = sv
                    elif sn == 6:
                        blob = sv
                if blob is not None and stat_names.get(sid, "").lower().replace(" ", "_") == "hlo_proto":
                    out[program_id] = blob
    return out


def hlo_module(hlo_proto: bytes) -> Dict:
    """``{"name": str, "computations": {id: {"name", "instructions": [
    {"name", "opcode", "op_name", "target", "calls": [ids]}]}}}`` of one
    ``HloProto``: ``op_name`` is the JAX name path of the instruction's
    metadata, ``target`` a custom call's target."""
    module = b""
    for num, _, val in fields(hlo_proto):
        if num == 1:
            module = val
    name, comps = "", {}
    for num, _, val in fields(module):
        if num == 1:
            name = val.decode()
        elif num == 3:
            cname, cid, instrs = "", None, []
            for cn, _, cv in fields(val):
                if cn == 1:
                    cname = cv.decode()
                elif cn == 5:
                    cid = cv
                elif cn == 2:
                    ins = {"name": "", "opcode": "", "op_name": "", "target": "",
                           "calls": []}
                    for iname, iwt, iv in fields(cv):
                        if iname == 1:
                            ins["name"] = iv.decode()
                        elif iname == 2:
                            ins["opcode"] = iv.decode()
                        elif iname == 7:
                            ins["op_name"] = next((mv.decode() for mn, _, mv in fields(iv)
                                                   if mn == 2), "")
                        elif iname == 28:
                            ins["target"] = iv.decode()
                        elif iname == 38:
                            ins["calls"].extend(_packed_ints(iv, iwt))
                    instrs.append(ins)
            comps[cid] = {"name": cname, "instructions": instrs}
    return {"name": name, "computations": comps}
