"""The program's own spans in a traced run.

While a JAX profiler session records, ``ServeEngine.step`` traces its
rounds into its fabric's tracer: for the ``LMBSystem`` the benchmark
builds, the process-wide ``repro.obs.trace.GLOBAL_TRACER``.  After a
``--trace 1`` run that ring holds the spans of the traced window's
rounds, and nothing else: the engine leaves the tracer off outside the
profiler's session.  A program that records no such spans gives the
readers nothing to read, and they return None.
"""

from __future__ import annotations

import collections
import sys
from typing import Dict, List, Optional


def recorded() -> Optional[List]:
    """The spans the program recorded, oldest first; None if there are
    none.  A ring that dropped spans is an error: a reader would read
    part of the window as if it were all of it."""
    from repro.obs.trace import GLOBAL_TRACER
    if GLOBAL_TRACER.dropped:
        raise RuntimeError(
            f"the program's span ring dropped {GLOBAL_TRACER.dropped} "
            f"spans (capacity {GLOBAL_TRACER.capacity})")
    return GLOBAL_TRACER.spans() or None


#: spans whose duration is modeled time, not the host's: left out
MODELED = ("link.xfer", "fault.transient")


class Tree:
    """Spans of host time by parent, for sums over a span's
    descendants."""

    def __init__(self, spans: List):
        spans = [s for s in spans if s.name not in MODELED]
        ids = {s.span_id for s in spans}
        self.kids: Dict[Optional[int], List] = collections.defaultdict(list)
        for s in spans:
            self.kids[s.parent_id if s.parent_id in ids else None].append(s)
        self.spans = spans

    def named(self, name: str) -> List:
        return [s for s in self.spans if s.name == name]

    def outermost(self, prefix: str, under=None) -> List:
        """The spans named with ``prefix`` (below ``under``, or
        anywhere) that no other such span encloses."""
        todo = list(self.kids[None if under is None else under.span_id])
        out = []
        while todo:
            s = todo.pop()
            if s.name.startswith(prefix):
                out.append(s)
            else:
                todo.extend(self.kids[s.span_id])
        return out

    def seconds(self, prefix: str, under=None) -> float:
        return sum(s.dur for s in self.outermost(prefix, under))

    def self_seconds(self, roots: List) -> Dict[str, float]:
        """Seconds by span name inside ``roots``, each span's own: its
        duration less its children's."""
        out: Dict[str, float] = collections.defaultdict(float)
        todo = list(roots)
        while todo:
            s = todo.pop()
            kids = self.kids[s.span_id]
            out[s.name] += s.dur - sum(k.dur for k in kids)
            todo.extend(kids)
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def log_split(what: str, split: Dict[str, float]) -> None:
    """One line of standard error: where the host's time went."""
    print(f"{what}: " + ", ".join(f"{k} {v:.6f}" for k, v in split.items()
                                  if v > 0), file=sys.stderr, flush=True)
