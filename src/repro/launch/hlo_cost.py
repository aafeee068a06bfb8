"""Roofline-grade cost extraction from optimized HLO text.

``compiled.cost_analysis()`` counts each ``while`` body ONCE, which silently
drops ~(n_layers x) of the FLOPs for scan-over-layers models (verified on
this container: a 7-iteration scan of a 2048-FLOP matmul reports 2050
FLOPs).  This parser walks the optimized HLO, multiplies loop bodies by
their ``known_trip_count``, and produces:

* ``flops``        — dot/convolution FLOPs, trip-count aware,
* ``bytes``        — HBM-traffic estimate: operand+output bytes of every
  top-level (unfused) instruction, trip-count aware,
* ``collectives``  — per-op records {op, bytes, axes, count, link_bytes}
  with the mesh axis set inferred from replica groups (supports both
  explicit ``{{0,4},{1,5}}`` and iota ``[4,2]<=[2,2,2]T(0,2,1)`` forms),
  where ``link_bytes`` applies the ring-algorithm factor
  (all-reduce 2(n-1)/n, all-gather/reduce-scatter (n-1)/n,
  all-to-all (n-1)/n, collective-permute 1).

All numbers are per device (HLO is the per-device SPMD program).
"""

from __future__ import annotations

import dataclasses
import json
import math
import re
from typing import Any

import numpy as np

__all__ = ["HLOCost", "analyze_hlo", "classify_groups", "collectives_over"]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1,
    "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = {
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
}

# bytes that traverse a link per device, as a multiple of the shard bytes
def _ring_factor(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n
    if op in ("all-gather", "reduce-scatter", "all-to-all"):
        return float(n - 1) / n
    if op in ("collective-permute", "collective-broadcast"):
        return 1.0
    return 1.0


@dataclasses.dataclass
class Instruction:
    name: str
    shape: str
    op: str
    args: list[str]
    attrs: str


@dataclasses.dataclass
class HLOCost:
    flops: float
    bytes: float
    collectives: list[dict]
    while_unknown_trip: int = 0

    def collective_bytes(self, axes: frozenset | None = None) -> float:
        """Sum of link-level bytes, optionally restricted to an axis set."""
        out = 0.0
        for c in self.collectives:
            if axes is None or set(c["axes"]) & set(axes):
                out += c["link_bytes"]
        return out

    def to_json(self) -> dict:
        return {
            "flops": self.flops,
            "bytes": self.bytes,
            "collectives": self.collectives,
            "while_unknown_trip": self.while_unknown_trip,
        }


# ---------------------------------------------------------------------------
# shape parsing
# ---------------------------------------------------------------------------


def _shape_bytes(shape: str) -> float:
    """Bytes of one HLO shape string (tuples summed)."""
    total = 0.0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(shape: str) -> list[int]:
    m = re.search(r"\w+\[([\d,]*)\]", shape)
    if not m or not m.group(1):
        return []
    return [int(d) for d in m.group(1).split(",")]


# ---------------------------------------------------------------------------
# module parsing
# ---------------------------------------------------------------------------


def _split_computations(text: str) -> dict[str, list[str]]:
    """Map computation name -> its instruction lines.

    Header lines look like ``%region_0.2 (arg: (s32[], f32[4,16])) -> ... {``
    (parameter lists contain nested parens, so the name is simply the token
    before the first '(' — no full-signature regex).
    """
    comps: dict[str, list[str]] = {}
    cur: str | None = None
    for line in text.splitlines():
        s = line.strip()
        # signature headers contain '->' (long ENTRY signatures also contain
        # '=' inside /*index=N*/ comments, so '=' cannot be the filter)
        if s.endswith("{") and "->" in s and "(" in s and " = " not in s:
            head = s.split("(", 1)[0].strip()
            if head.startswith("ENTRY"):
                head = head[len("ENTRY"):].strip()
            name = head.lstrip("%").strip()
            if name:
                cur = name
                comps[cur] = []
            continue
        if s == "}":
            cur = None
            continue
        if cur is not None and "=" in s:
            comps[cur].append(s)
    return comps


def _parse_instruction(line: str) -> Instruction | None:
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    m = re.match(r"%?([\w.\-]+)\s*=\s*(.*)$", s)
    if not m:
        return None
    name, rhs = m.group(1), m.group(2)
    # shape: balanced parens for tuples, else token up to first space
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        shape = rhs[: i + 1]
        rest = rhs[i + 1:].strip()
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        shape = rhs[:sp]
        rest = rhs[sp + 1:]
    m2 = re.match(r"([\w\-]+)\(", rest)
    if not m2:
        return None
    op = m2.group(1)
    # balanced-paren arg scan
    start = rest.find("(")
    depth = 0
    for i in range(start, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                break
    args_str = rest[start + 1: i]
    attrs = rest[i + 1:]
    args = [a.strip() for a in args_str.split(",") if a.strip()]
    return Instruction(name=name, shape=shape, op=op, args=args, attrs=attrs)


def _dot_flops(inst: Instruction, shapes: dict[str, str]) -> float:
    out_elems = 1.0
    for d in _shape_dims(inst.shape):
        out_elems *= d
    lhs = inst.args[0].lstrip("%") if inst.args else ""
    lhs_shape = shapes.get(lhs, "")
    lhs_dims = _shape_dims(lhs_shape)
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", inst.attrs)
    contract = 1.0
    if m and m.group(1) and lhs_dims:
        for d in m.group(1).split(","):
            di = int(d)
            if di < len(lhs_dims):
                contract *= lhs_dims[di]
    return 2.0 * out_elems * contract


def _conv_flops(inst: Instruction, shapes: dict[str, str]) -> float:
    # output elems x 2 x (kernel spatial x in_channels)
    out_elems = 1.0
    for d in _shape_dims(inst.shape):
        out_elems *= d
    rhs = inst.args[1].lstrip("%") if len(inst.args) > 1 else ""
    k_dims = _shape_dims(shapes.get(rhs, ""))
    k = 1.0
    for d in k_dims[:-1]:  # crude: all but output-feature dim
        k *= d
    return 2.0 * out_elems * k


def classify_groups(attrs: str, mesh_shape: dict[str, int]) -> tuple[frozenset, int]:
    """Infer which mesh axes a collective spans from its replica groups.

    Returns (axes, group_size).  Device id layout is row-major over the mesh
    axes in order (e.g. id = ((pod*D)+data)*M + model).
    """
    sizes = list(mesh_shape.values())
    names = list(mesh_shape.keys())
    total = int(np.prod(sizes))

    group0: list[int] | None = None
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", attrs)
    if m:
        group0 = [int(x) for x in m.group(1).split(",")]
    else:
        m = re.search(
            r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
            attrs,
        )
        if m:
            n_groups, per_group = int(m.group(1)), int(m.group(2))
            dims = [int(x) for x in m.group(3).split(",")]
            ids = np.arange(int(np.prod(dims))).reshape(dims)
            if m.group(4):
                perm = [int(x) for x in m.group(4).split(",")]
                ids = ids.transpose(perm)
            ids = ids.reshape(n_groups, per_group)
            group0 = ids[0].tolist()
    if not group0:
        return frozenset(), 1
    coords = []
    for dev in group0:
        c = []
        rem = dev
        for s in reversed(sizes):
            c.append(rem % s)
            rem //= s
        coords.append(tuple(reversed(c)))
    coords_arr = np.array(coords)
    axes = frozenset(
        names[i] for i in range(len(names))
        if len(set(coords_arr[:, i].tolist())) > 1
    )
    return axes, len(group0)


_COLLECTIVE_RE = re.compile(
    r"=.*?\s(" + "|".join(sorted(_COLLECTIVES)) + r")(?:-start)?\("
)


def collectives_over(text: str, mesh_shape: dict[str, int], axis: str) -> list[str]:
    """Ops of the collectives in ``text`` whose replica groups span ``axis``
    (async ``-start`` halves counted once, under the op's plain name)."""
    ops = []
    for line in text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m and axis in classify_groups(line, mesh_shape)[0]:
            ops.append(m.group(1))
    return ops


# ---------------------------------------------------------------------------
# main walk
# ---------------------------------------------------------------------------

_BYTES_OPS_SKIP = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "iota",
}


def analyze_hlo(text: str, mesh_shape: dict[str, int]) -> HLOCost:
    comps = _split_computations(text)
    parsed: dict[str, list[Instruction]] = {}
    shapes_by_comp: dict[str, dict[str, str]] = {}
    for cname, lines in comps.items():
        insts = []
        shapes: dict[str, str] = {}
        for l in lines:
            inst = _parse_instruction(l)
            if inst is None:
                continue
            insts.append(inst)
            shapes[inst.name] = inst.shape
        parsed[cname] = insts
        shapes_by_comp[cname] = shapes

    # entry = computation whose line had ENTRY; fall back to the largest
    entry = None
    for line in text.splitlines():
        if line.startswith("ENTRY"):
            m = re.match(r"ENTRY\s+%?([\w.\-]+)", line)
            if m:
                entry = m.group(1)
    if entry is None or entry not in parsed:
        entry = max(parsed, key=lambda c: len(parsed[c])) if parsed else ""

    collectives: list[dict] = []
    unknown_trips = [0]

    def _sliced_params(cname: str) -> dict[int, float]:
        """Fusion parameters consumed only through dynamic-slice/gather:
        charge the slice size, not the full operand (scan xs indexing)."""
        out: dict[int, float] = {}
        if cname not in parsed:
            return out
        uses: dict[str, list[tuple[str, float]]] = {}
        for inst in parsed[cname]:
            for a in inst.args:
                uses.setdefault(a.lstrip("%"), []).append(
                    (inst.op, _shape_bytes(inst.shape))
                )
        for line in comps.get(cname, []):
            m = re.match(
                r"\s*(?:ROOT )?%?([\w.\-]+) = \S+ parameter\((\d+)\)", line
            )
            if not m:
                continue
            pname, idx = m.group(1), int(m.group(2))
            u = uses.get(pname, [])
            if u and all(op in ("dynamic-slice", "gather") for op, _ in u):
                out[idx] = sum(b for _, b in u)
        return out

    def comp_cost(cname: str, mult: float, seen: tuple = ()) -> tuple[float, float]:
        if cname not in parsed or cname in seen:
            return 0.0, 0.0
        flops = 0.0
        nbytes = 0.0
        shapes = shapes_by_comp[cname]
        for inst in parsed[cname]:
            if inst.op == "dot":
                flops += _dot_flops(inst, shapes)
            elif inst.op == "convolution":
                flops += _conv_flops(inst, shapes)
            if inst.op == "dynamic-slice":
                # reads only the slice (= output), not the sliced operand —
                # counting operands here would charge every scan iteration
                # the full xs array (a ~1000x overcount for long scans)
                nbytes += 2.0 * _shape_bytes(inst.shape)
            elif inst.op == "dynamic-update-slice":
                # reads+writes the update region; the big aliased buffer is
                # untouched outside the window
                upd = inst.args[1].lstrip("%") if len(inst.args) > 1 else ""
                nbytes += 2.0 * _shape_bytes(shapes.get(upd, ""))
            elif inst.op == "gather":
                nbytes += 2.0 * _shape_bytes(inst.shape)
            elif inst.op == "scatter":
                upd = inst.args[-1].lstrip("%") if inst.args else ""
                nbytes += 2.0 * _shape_bytes(shapes.get(upd, ""))
            elif inst.op not in _BYTES_OPS_SKIP and inst.op != "fusion":
                nbytes += _shape_bytes(inst.shape)
                for a in inst.args:
                    nbytes += _shape_bytes(shapes.get(a.lstrip("%"), ""))
            if inst.op == "fusion":
                m = re.search(r"calls=%?([\w.\-]+)", inst.attrs)
                sliced: dict[int, float] = {}
                if m:
                    f_flops, _ = comp_cost(m.group(1), 1.0, seen + (cname,))
                    flops += f_flops
                    sliced = _sliced_params(m.group(1))
                nbytes += _shape_bytes(inst.shape)
                for i, a in enumerate(inst.args):
                    if i in sliced:
                        nbytes += sliced[i]
                    else:
                        nbytes += _shape_bytes(shapes.get(a.lstrip("%"), ""))
            elif inst.op == "while":
                mb = re.search(r"body=%?([\w.\-]+)", inst.attrs)
                mt = re.search(r'known_trip_count[":{]+n[":]+(\d+)', inst.attrs)
                trip = int(mt.group(1)) if mt else 1
                if not mt:
                    unknown_trips[0] += 1
                if mb:
                    b_f, b_b = comp_cost(mb.group(1), mult * trip, seen + (cname,))
                    flops += b_f * trip
                    nbytes += b_b * trip
            elif inst.op in ("call", "conditional", "async-start"):
                for m in re.finditer(
                    r"(?:to_apply|branch_computations=\{|calls)=?%?([\w.\-]+)", inst.attrs
                ):
                    c_f, c_b = comp_cost(m.group(1), mult, seen + (cname,))
                    flops += c_f
                    nbytes += c_b
            if inst.op in _COLLECTIVES:
                operand_bytes = sum(
                    _shape_bytes(shapes.get(a.lstrip("%"), "")) for a in inst.args
                )
                out_bytes = _shape_bytes(inst.shape)
                axes, gsize = classify_groups(inst.attrs, mesh_shape)
                # shard bytes: for all-gather the OUTPUT is the full tensor;
                # use max(in, out)/gsize-free convention: link bytes below.
                base = max(operand_bytes, out_bytes)
                link = base * _ring_factor(inst.op, gsize)
                collectives.append({
                    "op": inst.op,
                    "bytes": base * mult,
                    "link_bytes": link * mult,
                    "axes": sorted(axes),
                    "group_size": gsize,
                    "count": mult,
                })
        return flops, nbytes

    flops, nbytes = comp_cost(entry, 1.0)
    return HLOCost(
        flops=flops, bytes=nbytes, collectives=collectives,
        while_unknown_trip=unknown_trips[0],
    )
