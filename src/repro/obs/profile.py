"""Where a step's device time goes, read from a profile of the program.

Take a profile around any part of a run and read it back::

    with jax.profiler.trace("/tmp/prof"):
        trainer.run(20)
    trace = profile.load("/tmp/prof")
    profile.summary(trace, profile.count_steps(trace))

Two things in the program make the profile readable. The step program
names its phases with ``jax.named_scope`` (``grad``, ``optimizer``,
``ema``; on the mesh ``grad_stack`` and ``reduce``; ``attention`` in the
model), which XLA keeps as each op's ``op_name``; JAX's own transforms
add ``jvp(...)`` to the forward pass, ``transpose(jvp(...))`` to the
backward pass and ``rematted_computation`` to what ``jax.checkpoint``
recomputes. And the trainer loop's spans (``repro.obs.trace``) are
profiler annotations, on the host plane and on the device planes' clock.

:func:`load` turns the ``.xplane.pb`` into a plain form,

    {"window_ns": [start, end],
     "devices": {"TPU:0": [[op, start_ns, duration_ns], ...], ...},
     "scopes": {op: op_name path, ...},
     "program": [[span name, start_ns, duration_ns], ...]}

where ``op`` is the HLO instruction and its opcode (``fusion.536
fusion``), a device's ops are
those of its "XLA Ops" line, and an op's path is the ``tf_op`` stat of
the op's event metadata. ``ProfileData`` gives an event's own stats but
not its metadata's, so :func:`op_metadata` reads those from the file's
protobuf wire format. A fusion counts wholly to its root op's path,
which is what XLA gives it.
Everything else works on the plain form, so it is tested on a small
fixture (``tests/fixtures/trace_scopes.json``).
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:([A-Z]+:\d+)$")
OPS_LINE = "XLA Ops"
PROGRAM_PREFIXES = ("train/", "serve/")
_OPCODE = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
# ops that run others inside them: busy time, but no phase's own work
CONTROL = ("while", "conditional", "call")


def _scopes(path: str) -> List[str]:
    """The scope components of an op_name path: all but the last, which
    names the primitive (``jit(step)/optimizer/add`` -> optimizer)."""
    return path.split("/")[:-1]


def _has_scope(name: str) -> Callable[[str], bool]:
    return lambda path: name in _scopes(path)


# phase -> which op paths it counts. forward, backward, recompute,
# update and, on the mesh, grad_stack and reduce do not overlap;
# attention is a part of forward, backward and recompute.
PHASES: Dict[str, Callable[[str], bool]] = {
    "forward": lambda p: "jvp(" in p and "transpose(" not in p,
    "backward": lambda p: ("transpose(jvp(" in p
                           and "rematted_computation" not in p),
    "recompute": lambda p: "rematted_computation" in p,
    "update": lambda p: bool({"optimizer", "ema"} & set(_scopes(p))),
    "attention": _has_scope("attention"),
    "grad_stack": _has_scope("grad_stack"),
    "reduce": _has_scope("reduce"),
}
# the phases that partition a step (attention is inside three of them)
PARTITION = ("forward", "backward", "recompute", "update", "grad_stack",
             "reduce")


def _op(text: str) -> str:
    """``%fusion.536 = bf16[...] fusion(...)`` -> ``fusion.536 fusion``."""
    head, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest)
    return head.lstrip("%").strip() + (" " + m.group(1) if m else "")


# ---------------------------------------------------------------------------
# Event metadata, from the XSpace protobuf (tsl/profiler/protobuf/xplane.proto)
# ---------------------------------------------------------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message; a length-delimited value is
    a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_metadata(path: str) -> Dict[str, Dict[str, Dict[str, str]]]:
    """{device plane: {event name: {stat name: text}}} of the string and
    reference stats of each device plane's event metadata, keyed by the
    metadata's name and by its display name."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, Dict[str, str]]] = {}
    for field, plane in _fields(space):
        if field != 1:                                    # XSpace.planes
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:                                   # XPlane.name
                name = _text(v)
            elif pf == 4:                                 # event_metadata
                events.append(dict(_fields(v)).get(2, b""))
            elif pf == 5:                                 # stat_metadata
                m = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[m.get(1, 0)] = _text(m.get(2, b""))
        if not DEVICE_PLANE.match(name):
            continue
        table = out.setdefault(name, {})
        for ev in events:
            keys, stats = [], {}
            for ef, v in _fields(ev):
                if ef in (2, 4):                          # name, display_name
                    keys.append(_text(v))
                elif ef == 5:                             # XStat
                    st = dict(_fields(v))
                    key = stat_names.get(st.get(1, 0), "")
                    if 5 in st:                           # str_value
                        stats[key] = _text(st[5])
                    elif 7 in st:                         # ref_value
                        stats[key] = stat_names.get(st[7], "")
            for k in keys:
                if k:
                    table[k] = stats
    return out


def _path(text: str, meta: Dict[str, Dict[str, str]]) -> str:
    stats = meta.get(text) or meta.get(_op(text)) or {}
    # "name:type", the form TensorFlow ops take; JAX's type is empty
    return stats.get("tf_op", "").rsplit(":", 1)[0]


def load(path: str, window_span: Optional[str] = None) -> Dict:
    """The plain form of one profile (a ``.xplane.pb``, or the directory
    ``jax.profiler`` wrote it under). The window is the one host span
    named ``window_span`` where given, else from the first to the last
    event."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{path}: expected one .xplane.pb, "
                                    f"found {len(found)}")
        path = found[0]
    data = ProfileData.from_file(path)
    meta = op_metadata(path)
    devices: Dict[str, List] = {}
    scopes: Dict[str, str] = {}
    names: Dict[str, str] = {}
    program: List = []
    windows: List = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                evs = devices.setdefault(m.group(1), [])
                for e in line.events:
                    op = names.get(e.name)
                    if op is None:
                        op = names[e.name] = _op(e.name)
                        scopes[op] = _path(e.name, meta.get(plane.name, {}))
                    evs.append([op, float(e.start_ns), float(e.duration_ns)])
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    ev = [e.name, float(e.start_ns), float(e.duration_ns)]
                    if e.name.startswith(PROGRAM_PREFIXES):
                        program.append(ev)
                    elif e.name == window_span:
                        windows.append(ev)
    if window_span is not None:
        if len(windows) != 1:
            raise ValueError(f"expected one {window_span} span, found "
                             f"{len(windows)}")
        start, dur = windows[0][1], windows[0][2]
        window = [start, start + dur]
    else:
        every = [e for evs in devices.values() for e in evs] + program
        if not every:
            raise ValueError(f"{path}: no device ops and no program spans")
        window = [min(e[1] for e in every),
                  max(e[1] + e[2] for e in every)]
    return {"window_ns": window, "devices": devices, "scopes": scopes,
            "program": program}


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------


def _clip(events: Sequence, lo: float, hi: float) -> List[Interval]:
    out = []
    for _, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b))
    return out


def _union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _leaves(events: Sequence) -> List:
    """The ops that do a phase's work: all but the control-flow ops
    (a ``while`` spans its whole body). Other ops may overlap on the
    line, as a zero-length async copy inside a fusion does."""
    return [e for e in events if e[0].rsplit(" ", 1)[-1] not in CONTROL]


# ---------------------------------------------------------------------------
# Readings
# ---------------------------------------------------------------------------


def busy_s(trace: Dict) -> Dict[str, float]:
    """Seconds of the window in which some op ran, per chip."""
    lo, hi = trace["window_ns"]
    return {dev: _total(_union(_clip(evs, lo, hi))) * 1e-9
            for dev, evs in trace["devices"].items()}


def scope_s(trace: Dict, match: Callable[[str], bool]) -> Dict[str, float]:
    """Seconds of the window in which a leaf op whose op_name path
    ``match`` accepts ran, per chip. An op with no path counts nowhere."""
    lo, hi = trace["window_ns"]
    scopes = trace["scopes"]
    out = {}
    for dev, evs in trace["devices"].items():
        hit = [e for e in _leaves(evs)
               if scopes.get(e[0]) and match(scopes[e[0]])]
        out[dev] = _total(_union(_clip(hit, lo, hi))) * 1e-9
    return out


def _mean(values: Dict[str, float]) -> Optional[float]:
    return sum(values.values()) / len(values) if values else None


def phase_ms(trace: Dict, steps: int) -> Dict[str, Optional[float]]:
    """Milliseconds per step of each of :data:`PHASES`, mean over chips;
    None where no op of the window falls in the phase."""
    out = {}
    for name, match in PHASES.items():
        seconds = _mean(scope_s(trace, match))
        out[name] = (1e3 * seconds / steps
                     if seconds and steps > 0 else None)
    return out


def unscoped(trace: Dict, top: int = 10) -> List[List]:
    """The leaf ops that fall in no phase of :data:`PARTITION`, by
    seconds of the window (mean over chips), largest first."""
    lo, hi = trace["window_ns"]
    scopes = trace["scopes"]
    chips = max(len(trace["devices"]), 1)
    per_op: Dict[str, float] = collections.defaultdict(float)
    for evs in trace["devices"].values():
        for e in _leaves(evs):
            path = scopes.get(e[0], "")
            if any(PHASES[p](path) for p in PARTITION):
                continue
            per_op[e[0]] += _total(_clip([e], lo, hi)) * 1e-9 / chips
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return [[op, s, scopes.get(op, "")] for op, s in ranked]


def idle_gaps(trace: Dict) -> List[Tuple[str, str, float]]:
    """(chip, program span, seconds) of every stretch of the window in
    which no op ran on a chip, named by the innermost program span that
    held the middle of the stretch (``none`` where none did)."""
    lo, hi = trace["window_ns"]
    spans = [(s, s + d, name) for name, s, d in trace["program"]]
    out = []
    for dev, evs in trace["devices"].items():
        cur = lo
        for a, b in _union(_clip(evs, lo, hi)) + [(hi, hi)]:
            if a > cur:
                mid = (cur + a) / 2
                holding = [sp for sp in spans if sp[0] <= mid < sp[1]]
                name = (max(holding, key=lambda sp: sp[0])[2] if holding
                        else "none")
                out.append((dev, name, (a - cur) * 1e-9))
            cur = max(cur, b)
    return out


def summary(trace: Dict, steps: int, top: int = 10) -> Dict:
    """Every reading of one profile: ms per step of each phase, the
    share of busy time the partitioning phases cover, the largest ops
    outside them and the longest idle gaps."""
    phases = phase_ms(trace, steps)
    busy = _mean(busy_s(trace)) or 0.0
    covered = sum(phases[p] or 0.0 for p in PARTITION)
    busy_ms = 1e3 * busy / steps if steps > 0 else 0.0
    gaps = sorted(idle_gaps(trace), key=lambda g: -g[2])[:top]
    return {"steps": steps, "busy_ms_per_step": busy_ms,
            "phase_ms_per_step": phases,
            "covered_share": covered / busy_ms if busy_ms else None,
            "unscoped": unscoped(trace, top),
            "idle_gaps": [[f"{dev} {name}", s] for dev, name, s in gaps]}


def count_steps(trace: Dict) -> int:
    """``train/step`` spans that start in the window."""
    lo, hi = trace["window_ns"]
    return sum(1 for name, s, _ in trace["program"]
               if name == "train/step" and lo <= s < hi)
