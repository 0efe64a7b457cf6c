"""Which `jax.named_scope` an operation of the device trace belongs to.

A device op event is named by its HLO instruction (``%fusion.31 = ...``) and
carries no scope.  The profiler keeps the compiled programs beside the
events, though: the ``/host:metadata`` plane of the same ``.xplane.pb``
holds one serialized ``HloProto`` per program (stat "Hlo Proto"), and an
instruction there has ``metadata.op_name``, the path of scopes JAX traced it
under (``jit(flat_step)/jvp(vmap(NerrfNet))/gnn/gnn_layer_3/block_3/w_msg/
dot_general``; under ``transpose(jvp(...))`` for the backward pass).

`scopes_of_trace(path)` -> ``{program: {instruction: [op_name, ...]}}``.
An instruction's names are, in this order (read on a v5e, PR 25: 27 % of
`train-1024`'s device time is in instructions the compiler made late and
left without metadata):

1. its own ``op_name``;
2. else those of the instructions of the computations it calls (a
   ``kCustom`` fusion around a matrix product has none itself, the product
   inside it has);
3. else those of its nearest named users, then of its nearest named
   operands, at most `HOPS` instructions away (the zero-filled buffers a
   scan saves its activations into are ``broadcast.N.clone`` without a
   name; the ``while`` they feed has one).

The reader of the protobuf wire format is kept here because JAX's
``ProfileData`` does not expose event metadata and the generated protobuf
classes come only with TensorFlow, whose import would cost every traced run
half a minute.  Field numbers (tsl/profiler/protobuf/xplane.proto,
xla/service/hlo.proto, xla/xla_data.proto): XSpace.planes 1; XPlane.name 2,
.event_metadata 4 (map entry: key 1, value 2), .stat_metadata 5;
XEventMetadata.name 2, .stats 5; XStat.metadata_id 1, .bytes_value 6;
XStatMetadata.id 1, .name 2; HloProto.hlo_module 1;
HloModuleProto.computations 3; HloComputationProto.instructions 2, .id 5;
HloInstructionProto.name 1, .metadata 7, .id 35, .operand_ids 36,
.called_computation_ids 38; OpMetadata.op_name 2.
"""

from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
HOPS = 4


# --------------------------------------------------------------------------
# protobuf wire format
# --------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield number, value


def _first(buf, number: int, default=None):
    for k, v in fields(buf):
        if k == number:
            return v
    return default


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace") if view is not None else ""


def _ints(value) -> List[int]:
    """A repeated integer field: one varint, or a packed run of them."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


# --------------------------------------------------------------------------
# HLO module -> names per instruction
# --------------------------------------------------------------------------

def read_module(hlo_proto) -> Dict[int, List[dict]]:
    """{computation id: [instruction, ...]} of one serialized HloProto; an
    instruction is {"name", "id", "op_name", "operands", "calls"}."""
    module = _first(hlo_proto, 1)
    out: Dict[int, List[dict]] = {}
    if module is None:
        return out
    for k, computation in fields(module):
        if k != 3:
            continue
        cid, instructions = None, []
        for k2, v2 in fields(computation):
            if k2 == 5:
                cid = v2
            elif k2 == 2:
                inst = {"name": "", "id": None, "op_name": "",
                        "operands": [], "calls": []}
                for k3, v3 in fields(v2):
                    if k3 == 1:
                        inst["name"] = _text(v3)
                    elif k3 == 7:
                        inst["op_name"] = _text(_first(v3, 2))
                    elif k3 == 35:
                        inst["id"] = v3
                    elif k3 == 36:
                        inst["operands"] += _ints(v3)
                    elif k3 == 38:
                        inst["calls"] += _ints(v3)
                instructions.append(inst)
        out[cid] = instructions
    return out


def instruction_scopes(module: Dict[int, List[dict]]) -> Dict[str, List[str]]:
    """{instruction name: its op_names} by the three rules above;
    instructions that none of them names are left out."""
    inside: Dict[int, List[str]] = {}

    def own_or_called(inst, depth=0) -> List[str]:
        if inst["op_name"]:
            return [inst["op_name"]]
        if depth > HOPS:
            return []
        names: List[str] = []
        for cid in inst["calls"]:
            if cid not in inside:
                inside[cid] = []          # a cycle cannot happen; be safe
                inside[cid] = [n for sub in module.get(cid, ())
                               for n in own_or_called(sub, depth + 1)]
            names += inside[cid]
        return names

    out: Dict[str, List[str]] = {}
    for instructions in module.values():
        by_id = {i["id"]: i for i in instructions}
        users: Dict[int, List[dict]] = {}
        for inst in instructions:
            for op in inst["operands"]:
                users.setdefault(op, []).append(inst)
        direct = {i["id"]: own_or_called(i) for i in instructions}

        def neighbours(inst, step):
            seen, frontier = {inst["id"]}, [inst]
            for _ in range(HOPS):
                nxt = [n for f in frontier for n in step(f)
                       if n["id"] not in seen]
                seen.update(n["id"] for n in nxt)
                names = [n for x in nxt for n in direct[x["id"]]]
                if names or not nxt:
                    return names
                frontier = nxt
            return []

        for inst in instructions:
            names = (direct[inst["id"]]
                     or neighbours(inst, lambda x: users.get(x["id"], ()))
                     or neighbours(inst, lambda x: [
                         by_id[o] for o in x["operands"] if o in by_id]))
            if names:
                out[inst["name"]] = names
    return out


# --------------------------------------------------------------------------
# the trace's metadata plane
# --------------------------------------------------------------------------

def hlo_protos(xplane_path: str) -> Dict[str, memoryview]:
    """{program name as the trace has it, e.g. ``jit_flat_step(123)``: its
    serialized HloProto}; empty where the trace keeps none."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, memoryview] = {}
    for k, plane in fields(space):
        if k != 1 or _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        stat_id = None
        for k2, entry in fields(plane):
            meta = _first(entry, 2) if k2 == 5 else None
            if meta is not None and _text(_first(meta, 2)) == HLO_PROTO_STAT:
                stat_id = _first(meta, 1, _first(entry, 1))
        for k2, entry in fields(plane):
            meta = _first(entry, 2) if k2 == 4 else None
            if meta is None:
                continue
            for k3, stat in fields(meta):
                if k3 == 5 and (stat_id is None
                                or _first(stat, 1) == stat_id):
                    blob = _first(stat, 6)
                    if blob is not None:
                        out[_text(_first(meta, 2))] = blob
    return out


def scopes_of_trace(xplane_path: str) -> Dict[str, Dict[str, List[str]]]:
    return {program: instruction_scopes(read_module(blob))
            for program, blob in hlo_protos(xplane_path).items()}
