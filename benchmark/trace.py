"""Reduce a profiler trace (xplane) to what the benchmark reports.

* busy and idle time on the device: the union of the intervals in which
  an operation ran on a chip, over the benchmark's own traced span (the
  host event ``bench.traced_window``), averaged over the chips;
* time per device operation, by its short name (``op_name``);
* the longest idle gaps, each set against the host event that overlaps
  it most (the benchmark's own spans and the runtime's).

``reduce_events`` works on plain tuples, so its tests need no chip.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

WINDOW_SPAN = "bench.traced_window"
DEVICE_OPS_LINE = "XLA Ops"
TOP = 10

# (plane, line, name, start_ns, end_ns)
Event = Tuple[str, str, str, float, float]


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData

    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    return out


_HLO = re.compile(r"^%?(\S+) = .*? ([a-z][a-z0-9_-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_name(name: str) -> str:
    """A device op's short name: the trace gives the whole HLO
    instruction; keep its name, its opcode and a custom call's target
    (``_lambda_.2 custom-call tpu_custom_call`` for a Pallas kernel)."""
    m = _HLO.match(name)
    if m is None:
        return name[:80]
    t = _TARGET.search(name)
    return " ".join((m.group(1), m.group(2)) + ((t.group(1),) if t else ()))


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and \
        not plane.startswith("/device:CPU")


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce_events(events: List[Event]) -> Dict:
    span = [e for e in events if e[2] == WINDOW_SPAN]
    if span:
        lo, hi = span[0][3], span[0][4]
    else:
        lo = min(e[3] for e in events)
        hi = max(e[4] for e in events)
    per_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    op_s: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, int] = defaultdict(int)
    host: List[Event] = []
    for e in events:
        plane, line, name, a, b = e
        if is_device(plane):
            if line != DEVICE_OPS_LINE or b <= lo or a >= hi:
                continue
            per_dev[plane].append((a, b))
            name = op_name(name)
            op_s[name] += (min(b, hi) - max(a, lo)) / 1e9
            op_n[name] += 1
        elif plane.startswith("/host:") and name != WINDOW_SPAN and \
                b > a:
            host.append(e)
    window = (hi - lo) / 1e9
    merged = {d: _clip(_union(iv), lo, hi) for d, iv in per_dev.items()}
    busy = sum(sum(b - a for a, b in iv) for iv in merged.values())
    busy_s = busy / 1e9 / max(1, len(merged))
    gaps = []
    for iv in merged.values() or [[]]:
        edges = [lo] + [x for a, b in iv for x in (a, b)] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for a, b in gaps[:TOP]:
        best, over = "no host event", 0.0
        for e in host:
            o = min(b, e[4]) - max(a, e[3])
            if o > over:
                best, over = e[2], o
        idle.append([best, (b - a) / 1e9])
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:TOP]
    return {"window_s": window, "busy_s": busy_s,
            "device_count": len(merged),
            "ops": dict(op_s), "op_counts": dict(op_n),
            "breakdown": {"device_ops": [[n, s] for n, s in top],
                          "idle_gaps": idle}}


def reduce_dir(trace_dir: str) -> Dict:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    return reduce_events(load(sorted(paths)[-1]))
