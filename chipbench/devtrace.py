"""The device trace of a run, and its reduction to numbers.

The harness records a profiler trace of the measured window and marks
the window and each of its own calls into the program with
``jax.profiler.TraceAnnotation`` names that start with ``bench.``.
``Trace.load`` keeps only what the reduction needs: the device planes'
op events and the host's ``bench.*`` annotations.  The reduction (busy
union, idle share, op time by name, idle gaps by what the host was
doing) works on that plain record, which ``tests/`` also holds as a small
recorded file.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
BENCH = "bench."


@dataclass
class Trace:
    # per device plane: [(name, start_ns, dur_ns, program)] of the op line
    ops: Dict[str, List[Tuple[str, float, float, str]]] = field(
        default_factory=dict)
    # host annotations: [(name, start_ns, dur_ns)]
    marks: List[Tuple[str, float, float]] = field(default_factory=list)

    # -- loading -----------------------------------------------------------
    @classmethod
    def load(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        out = cls()
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    evs = out.ops.setdefault(plane.name, [])
                    for e in line.events:
                        st = dict(e.stats)
                        evs.append((e.name, float(e.start_ns),
                                    float(e.duration_ns),
                                    str(st.get("hlo_module", ""))))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(BENCH):
                            out.marks.append((e.name, float(e.start_ns),
                                              float(e.duration_ns)))
        return out

    def to_json(self) -> dict:
        return {"ops": self.ops, "marks": self.marks}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(ops={k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                   marks=[tuple(m) for m in d["marks"]])

    # -- the window --------------------------------------------------------
    def window(self) -> Tuple[float, float]:
        """(start_ns, end_ns) of the ``bench.window`` annotation."""
        w = [m for m in self.marks if m[0] == BENCH + "window"]
        if not w:
            raise ValueError("trace holds no bench.window annotation")
        return w[0][1], w[0][1] + w[0][2]

    def window_s(self) -> float:
        a, b = self.window()
        return (b - a) * 1e-9

    def _clipped(self, plane: str) -> List[Tuple[float, float]]:
        a, b = self.window()
        out = []
        for _, s, d, _ in self.ops.get(plane, ()):
            lo, hi = max(s, a), min(s + d, b)
            if hi > lo:
                out.append((lo, hi))
        return out

    def busy_intervals(self, plane: str) -> List[Tuple[float, float]]:
        """The union of the plane's op intervals inside the window."""
        merged: List[Tuple[float, float]] = []
        for lo, hi in sorted(self._clipped(plane)):
            if merged and lo <= merged[-1][1]:
                if hi > merged[-1][1]:
                    merged[-1] = (merged[-1][0], hi)
            else:
                merged.append((lo, hi))
        return merged

    def busy_s(self) -> Optional[float]:
        """Seconds in which an op ran, averaged over the device planes."""
        planes = [p for p in self.ops if self.ops[p]]
        if not planes:
            return None
        tot = sum(sum(hi - lo for lo, hi in self.busy_intervals(p))
                  for p in planes)
        return tot / len(planes) * 1e-9

    def idle_share(self) -> Optional[float]:
        busy = self.busy_s()
        if busy is None:
            return None
        return 1.0 - busy / self.window_s()

    # -- ops ---------------------------------------------------------------
    def op_seconds(self, match: Callable[[str, str], bool]) -> float:
        """Summed device time (clipped to the window, over all planes) of
        the ops for which ``match(op_name, program_name)`` holds."""
        a, b = self.window()
        tot = 0.0
        for evs in self.ops.values():
            for name, s, d, prog in evs:
                if match(name, prog):
                    tot += max(0.0, min(s + d, b) - max(s, a))
        return tot * 1e-9

    def top_ops(self, n: int = 10) -> List[List]:
        """The ops that took most device time, keyed by program and op."""
        a, b = self.window()
        acc: Dict[str, float] = {}
        for evs in self.ops.values():
            for name, s, d, prog in evs:
                t = max(0.0, min(s + d, b) - max(s, a))
                if t > 0:
                    op = name.split(" = ")[0].lstrip("%")
                    key = f"{prog}/{op}" if prog else op
                    acc[key] = acc.get(key, 0.0) + t
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest gaps with no op on the first device, each named by
        the innermost ``bench.*`` annotation around its midpoint."""
        planes = sorted(p for p in self.ops if self.ops[p])
        if not planes:
            return []
        a, b = self.window()
        busy = self.busy_intervals(planes[0])
        gaps, cur = [], a
        for lo, hi in busy:
            if lo > cur:
                gaps.append((cur, lo))
            cur = max(cur, hi)
        if b > cur:
            gaps.append((cur, b))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for lo, hi in gaps[:n]:
            mid = 0.5 * (lo + hi)
            around = [m for m in self.marks if m[0] != BENCH + "window"
                      and m[1] <= mid <= m[1] + m[2]]
            name = min(around, key=lambda m: m[2])[0] if around \
                else "outside any harness call"
            out.append([name, (hi - lo) * 1e-9])
        return out


def save(trace: Trace, path: str) -> None:
    """Write the plain record (how ``tests/recorded/`` slices are made)."""
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
