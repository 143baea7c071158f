"""From the profiler's trace to numbers: the reduction every cell shares.

``from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into a plain form,

    {"window_ns": [start, end],
     "devices": {"TPU:0": [[op, start_ns, duration_ns], ...], ...},
     "host": [[span name, start_ns, duration_ns], ...],
     "custom_calls": {op name: HLO text of that custom call, ...}}

where the device events are the ops of each chip's "XLA Ops" line, the
host events are the benchmark's own ``bench/...`` annotations, and the
window is the ``bench/window`` annotation. An op is named by its HLO
instruction and opcode (``fusion.536 fusion``; a custom call adds its
target and kernel name), not by the whole HLO text the profiler gives.
The line holds loops (``while``) as well as the ops inside them; an op
that contains others is a container and counts only towards busy time.
Everything below works on that form, so it is tested on a small
recorded fixture.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:(TPU:\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")


_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KERNEL = re.compile(r'"(?:kernel_name|name)"\s*:\s*"([^"]+)"')


def op_name(text: str) -> str:
    """``%fusion.536 = bf16[...] fusion(...), ...`` -> ``fusion.536
    fusion``; a custom call adds its target and, where the backend
    config names one, its kernel."""
    head, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest)
    name = head.lstrip("%") + (" " + m.group(1) if m else "")
    if m and m.group(1) == "custom-call":
        for pattern in (_TARGET, _KERNEL):
            found = pattern.search(rest)
            if found:
                name += " " + found.group(1)
    return name


def from_xplane(path: str) -> Dict:
    """The plain form of one profile (a ``.xplane.pb`` file, or the
    directory ``jax.profiler`` wrote it under)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{path}: expected one .xplane.pb, "
                                    f"found {len(found)}")
        path = found[0]
    data = ProfileData.from_file(path)
    devices: Dict[str, List] = {}
    host: List = []
    names: Dict[str, str] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(m.group(1), []).extend(
                    [names.get(e.name) or names.setdefault(
                        e.name, op_name(e.name)),
                     float(e.start_ns), float(e.duration_ns)]
                    for e in line.events)
            elif not m and plane.name.startswith("/host"):
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    windows = [h for h in host if h[0] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    start, dur = windows[0][1], windows[0][2]
    # the HLO text of each custom call: what a kernel's reader matches on
    kernels = {name: text[:4000] for text, name in names.items()
               if " custom-call" in name}
    return {"window_ns": [start, start + dur], "devices": devices,
            "host": host, "custom_calls": kernels}


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------


def clip(events: Sequence, lo: float, hi: float) -> List[Interval]:
    """[start, end) of each event, cut to the window; empty ones dropped."""
    out = []
    for _, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of union(a) that no interval of b covers."""
    out = []
    b = union(b)
    j = 0
    for lo, hi in union(a):
        while j < len(b) and b[j][1] <= lo:
            j += 1
        cur, k = lo, j
        while k < len(b) and b[k][0] < hi:
            x, y = b[k]
            if x > cur:
                out.append((cur, x))
            cur = max(cur, y)
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# ---------------------------------------------------------------------------
# What the metrics read
# ---------------------------------------------------------------------------


def leaves(events: Sequence) -> List:
    """The events that contain no other event whole (a loop's body ops,
    not the loop); ops that merely overlap are both kept."""
    def end(i):
        return events[i][1] + events[i][2]

    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    container = set()
    stack: List[int] = []
    for i in order:
        while stack and end(stack[-1]) <= events[i][1]:
            stack.pop()
        if stack and end(stack[-1]) >= end(i):
            container.add(stack[-1])
        stack.append(i)
    return [e for i, e in enumerate(events) if i not in container]


def window_s(trace: Dict) -> float:
    lo, hi = trace["window_ns"]
    return (hi - lo) * 1e-9


def busy_s(trace: Dict) -> Dict[str, float]:
    """Seconds of the window in which some op ran, per chip."""
    lo, hi = trace["window_ns"]
    return {dev: total(union(clip(evs, lo, hi))) * 1e-9
            for dev, evs in trace["devices"].items()}


def op_s(trace: Dict, match: Callable[[str], bool]) -> Dict[str, float]:
    """Summed duration, inside the window, of the ops ``match`` accepts,
    per chip."""
    lo, hi = trace["window_ns"]
    return {dev: total(clip([e for e in leaves(evs) if match(e[0])], lo, hi))
            * 1e-9 for dev, evs in trace["devices"].items()}


def exposed_s(trace: Dict, match: Callable[[str], bool] = None
              ) -> Dict[str, float]:
    """Seconds in which an op ``match`` accepts (a collective, by
    default) runs on a chip and no other op runs there, per chip."""
    match = match or (lambda name: bool(COLLECTIVE.search(name)))
    lo, hi = trace["window_ns"]
    out = {}
    for dev, evs in trace["devices"].items():
        evs = leaves(evs)
        coll = clip([e for e in evs if match(e[0])], lo, hi)
        other = clip([e for e in evs if not match(e[0])], lo, hi)
        out[dev] = total(subtract(coll, other)) * 1e-9
    return out


def idle_gaps(trace: Dict) -> List[Tuple[str, str, float]]:
    """(chip, host span, seconds) of every stretch of the window in which
    no op ran on a chip, named by the innermost ``bench/`` span that
    held the middle of the stretch (``none`` where none did)."""
    lo, hi = trace["window_ns"]
    spans = [(s, s + d, name) for name, s, d in trace["host"]
             if name != WINDOW_SPAN]
    out = []
    for dev, evs in trace["devices"].items():
        busy = union(clip(evs, lo, hi))
        for a, b in subtract([(lo, hi)], busy):
            mid = (a + b) / 2
            holding = [sp for sp in spans if sp[0] <= mid < sp[1]]
            name = (max(holding, key=lambda sp: sp[0])[2] if holding
                    else "none")
            out.append((dev, name, (b - a) * 1e-9))
    return out


def breakdown(trace: Dict, top: int = 10) -> Dict[str, List]:
    """The ops (containers left out) that took most device time
    (seconds per chip, summed over the window and averaged over chips)
    and the longest idle gaps."""
    lo, hi = trace["window_ns"]
    per_op: Dict[str, float] = collections.defaultdict(float)
    chips = max(len(trace["devices"]), 1)
    for evs in trace["devices"].values():
        for name, start, dur in leaves(evs):
            a, b = max(start, lo), min(start + dur, hi)
            if b > a:
                per_op[name] += (b - a) * 1e-9 / chips
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace), key=lambda g: -g[2])[:top]
    return {"device_ops": [[name, s] for name, s in ops],
            "idle_gaps": [[f"{dev} {name}", s] for dev, name, s in gaps]}


def mean(values: Dict[str, float]) -> Optional[float]:
    return sum(values.values()) / len(values) if values else None
