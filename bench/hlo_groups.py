"""Which mesh axes a compiled collective spans, read from its replica groups.

The benchmark's own copy of ``classify_groups`` / ``collectives_over`` from
``repro.launch.hlo_cost``, so that what counts as a collective over the
``pod`` axis cannot move with the program.  Device ids are row-major over
the mesh axes in order (id = ((pod * D) + data) * M + model).
"""

from __future__ import annotations

import re

import numpy as np

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)

_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?(\S+)\s*=.*?\s(" + "|".join(sorted(COLLECTIVES))
    + r")(?:-start)?\("
)


def classify_groups(attrs: str, mesh_shape: dict[str, int]) -> tuple[frozenset, int]:
    """(axes spanned, group size) of the collective whose HLO is ``attrs``."""
    sizes = list(mesh_shape.values())
    names = list(mesh_shape.keys())
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
                ids = ids.transpose([int(x) for x in m.group(4).split(",")])
            group0 = ids.reshape(n_groups, per_group)[0].tolist()
    if not group0:
        return frozenset(), 1
    coords = []
    for dev in group0:
        c = []
        for s in reversed(sizes):
            c.append(dev % s)
            dev //= s
        coords.append(tuple(reversed(c)))
    arr = np.array(coords)
    axes = frozenset(
        names[i] for i in range(len(names)) if len(set(arr[:, i].tolist())) > 1
    )
    return axes, len(group0)


def collectives_over(text: str, mesh_shape: dict[str, int], axis: str) -> dict[str, str]:
    """{HLO instruction name: op} of the collectives in ``text`` whose
    replica groups span ``axis`` (an async pair is named by its start)."""
    found = {}
    for line in text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m and axis in classify_groups(line, mesh_shape)[0]:
            found[m.group(1).lstrip("%")] = m.group(2)
    return found
