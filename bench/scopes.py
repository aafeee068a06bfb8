"""Device time per named scope of the program, read from the compiled step.

The program wraps each layer of the train step in ``jax.named_scope``
(``attn``, ``moe_router``, ``moe_dispatch``, ``moe_experts``,
``moe_combine``, ``embed``, ``logits``, ``adam``, ``pod_exchange``), and
JAX writes that name into the ``op_name`` metadata of every HLO
instruction traced inside it, through ``scan``, ``checkpoint`` and the
forward and backward passes alike.  Matching the instruction names of the
trace's device ops (``Summary.op_s``) to the scope in their metadata gives
each layer's device self time per step.
"""

from __future__ import annotations

import collections
import re

SCOPES = frozenset({"embed", "attn", "moe_router", "moe_dispatch", "moe_experts",
                    "moe_combine", "logits", "adam", "pod_exchange"})

# a computation's first line, an instruction line, the op_name of its
# metadata and the computations it calls (a fusion's body)
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
# a transform around a path component: "jvp(x)", "transpose(jvp(x))"
_WRAPPER = re.compile(r"^[\w\-]+\((.*)\)$")

# {cell name: {instruction name: scope}}: the readers of one run share one
# parse of the step
_PARSED: dict[str, dict[str, str]] = {}


def scope_of(op_name: str) -> str | None:
    """The innermost known scope on the path ``op_name``, else ``None``."""
    found = None
    for part in op_name.split("/"):
        while part not in SCOPES and (m := _WRAPPER.match(part)):
            part = m.group(1)
        if part in SCOPES:
            found = part
    return found


def _instructions(hlo_text: str) -> list[tuple[str, str, str | None, list[str]]]:
    """(computation, name, op_name or None, called computations) of every
    instruction of ``hlo_text``, in order."""
    out, comp = [], None
    for line in hlo_text.splitlines():
        if comp is None:
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
        elif line.startswith("}"):
            comp = None
        elif m := _INSTRUCTION.match(line):
            meta = _OP_NAME.search(line)
            out.append((comp, m.group(1), meta.group(1) if meta else None,
                        _CALLS.findall(line)))
    return out


def _majority(scopes) -> str | None:
    votes = collections.Counter(s for s in scopes if s)
    return votes.most_common(1)[0][0] if votes else None


def instruction_scopes(hlo_text: str, names_from: str | None = None) -> dict[str, str]:
    """{instruction name: scope} over every computation of ``hlo_text``.

    An instruction takes the scope its metadata names.  One with no
    metadata at all (the TPU compiler leaves some fusions bare, the MoE
    scatters among them) takes the scope most instructions of the
    computations it calls carry.  ``names_from`` is another compile of the
    same program, identical but for metadata and instruction numbering:
    the scopes are then given under its instruction names, matched by
    position."""
    insts = _instructions(hlo_text)
    body = collections.defaultdict(list)
    for comp, _, op_name, calls in insts:
        body[comp].append((op_name, calls))
    memo: dict[str, str | None] = {}

    def of_computation(comp):
        if comp not in memo:
            memo[comp] = None
            memo[comp] = _majority(resolve(*i) for i in body[comp])
        return memo[comp]

    def resolve(op_name, calls):
        if op_name is not None:
            return scope_of(op_name)
        return _majority(of_computation(c) for c in calls)

    names = [name for _, name, _, _ in insts]
    if names_from is not None:
        other = [name for _, name, _, _ in _instructions(names_from)]
        if len(other) == len(names):
            names = other
    return {n: s for n, (_, _, op_name, calls) in zip(names, insts)
            if (s := resolve(op_name, calls))}


def parsed(ctx) -> dict[str, str]:
    """``instruction_scopes`` of the cell's compiled step, parsed once.

    JAX's persistent cache keys a program without its metadata, so the
    window may have run an executable that a program differing only in
    scope names compiled (the parent commit's, say), with that program's
    metadata and instruction numbering.  The step is compiled twice: as
    the cache holds it, for the names the trace's ops carry, and keyed
    with its metadata, for this program's scopes."""
    if ctx.cell.name not in _PARSED:
        import jax

        ran = ctx.step_hlo()
        key = "jax_compilation_cache_include_metadata_in_key"
        before = getattr(jax.config, key)
        jax.config.update(key, True)
        try:
            text = ctx.step_hlo()
        finally:
            jax.config.update(key, before)
        _PARSED[ctx.cell.name] = instruction_scopes(text, names_from=ran)
    return _PARSED[ctx.cell.name]


def scope_ms(ctx, scopes) -> float | None:
    """Device self time per step of the ops in ``scopes``, ms; ``None``
    where the step has no instruction in them."""
    s = ctx.summary
    named = parsed(ctx)
    if not s.steps or not any(v in scopes for v in named.values()):
        return None
    return sum(t for op, t in s.op_s.items() if named.get(op) in scopes) / s.steps * 1e3


def unscoped_ms(ctx) -> float | None:
    """Device self time per step of the ops in no known scope, ms; ``None``
    where the step has no named scope at all (a program without them)."""
    s = ctx.summary
    named = parsed(ctx)
    if not s.steps or not named:
        return None
    return sum(t for op, t in s.op_s.items() if op not in named) / s.steps * 1e3
