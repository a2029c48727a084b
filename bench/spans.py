"""The program's spans (``span`` events of its tracer), as the harness keeps
them: ``(start, end, name, parent, uid)`` on the host clock, in the order
they ended. A span's descendants are the spans that lie inside it in time
(the serving loop runs on one thread). Spans whose name ends in ``.wait``
cover the host blocked on a device->host read; the rest of a span is host
work during which it put nothing new on the chip.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

import numpy as np

WAIT = ".wait"

Span = Tuple[float, float, str, str, Optional[int]]


class Spans:
    """Spans sorted by start, for containment queries."""

    def __init__(self, spans: List[Span]):
        self.spans = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
        self.starts = [sp[0] for sp in self.spans]

    def named(self, name: str) -> List[Span]:
        return [sp for sp in self.spans if sp[2] == name]

    def inside(self, root: Span) -> List[Span]:
        """The spans that lie inside ``root`` (not ``root`` itself), by
        start."""
        i = bisect.bisect_left(self.starts, root[0])
        j = bisect.bisect_right(self.starts, root[1])
        return [sp for sp in self.spans[i:j]
                if sp[1] <= root[1] and sp is not root]

    def host_s(self, root: Span) -> float:
        """``root``'s duration less its ``.wait`` descendants'."""
        return (root[1] - root[0]) - sum(
            sp[1] - sp[0] for sp in self.inside(root) if sp[2].endswith(WAIT))


def median_host_ms(spans: List[Span], name: str) -> Optional[float]:
    """Median over the spans called ``name`` of their host time, in ms."""
    idx = Spans(spans)
    roots = idx.named(name)
    if not roots:
        return None
    return float(np.median([idx.host_s(r) for r in roots])) * 1e3


def tree(spans: List[Span], root: Span) -> str:
    """``root`` and its descendants as one line: each name with its
    duration in ms, children in brackets, siblings of one name summed
    (``xN`` counts them)."""
    idx = Spans(spans)
    below = idx.inside(root)
    # a span's parent is the shortest earlier span that contains it
    kids: Dict[int, List[int]] = {}
    nodes = [root] + below
    for k, sp in enumerate(nodes[1:], 1):
        parent = 0
        for p in range(k - 1, 0, -1):
            q = nodes[p]
            if q[0] <= sp[0] and sp[1] <= q[1]:
                parent = p
                break
        kids.setdefault(parent, []).append(k)

    def render(ids: List[int]) -> str:
        groups: Dict[str, List[int]] = {}
        for k in ids:
            groups.setdefault(nodes[k][2], []).append(k)
        parts = []
        for name, ks in groups.items():
            ms = sum(nodes[k][1] - nodes[k][0] for k in ks) * 1e3
            text = f"{name}{f' x{len(ks)}' if len(ks) > 1 else ''} {ms:.3f}"
            sub = [c for k in ks for c in kids.get(k, [])]
            parts.append(f"{text} [{render(sub)}]" if sub else text)
        return ", ".join(parts)

    return render([0])
