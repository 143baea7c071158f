"""Host-side tracer: nested spans on the profiler's clock, ring-buffered,
Chrome-trace export.

The measurement substrate of the telemetry layer (docs/observability.md).
Every span, with a :class:`Tracer` or without one, enters a
``jax.profiler.TraceAnnotation`` of the same name: when a profile of the
program is taken (``jax.profiler.trace``), the loop's spans land on the
host plane beside the device planes, on one clock, so a gap on a chip can
be put down to the host span that held it. The annotation carries the
name only; a span's ``args`` stay in the tracer's ring.

A :class:`Tracer` additionally records *host wall-clock* spans via
``time.perf_counter_ns``; device work is bracketed by the callers with
``jax.block_until_ready`` fences **at chunk edges only**, so the fused
``lax.scan`` hot loop is never broken into per-step dispatches just to
be observable. Events live in a bounded ring (old events drop, the
``dropped`` counter records how many) and export as Chrome-trace JSON —
load the file at https://ui.perfetto.dev or chrome://tracing.

Disabled tracing records nothing and fences nothing: pass no tracer and
every instrumentation site sees :data:`NULL`, whose ``span()`` is one
inactive ``TraceAnnotation`` (~0.6 µs when no profiler runs). The
overhead test in ``tests/test_obs.py`` holds that path under 2% of the
chunked training loop.

Span names are registered in :data:`SPAN_NAMES`; the docs drift guard
(``tests/test_docs.py``) keeps every name documented in
docs/observability.md. Dependencies: the stdlib and ``jax.profiler``.
"""
from __future__ import annotations

import collections
import json
import time
from typing import Any, Deque, Dict, List, Optional

from jax.profiler import TraceAnnotation

# The span taxonomy: every name an instrumentation site emits. cat is
# the prefix; the drift guard pins each name into docs/observability.md.
SPAN_NAMES = (
    # train/loop.py
    "train/step",             # legacy per-step dispatch (chunk_size=1)
    "train/chunk",            # one fused K-step lax.scan dispatch
    "train/select",           # arrival draw + the N-of-N+b mask
    "train/data_wait",        # prefetcher / batch staging and upload
    "train/dispatch",         # the jitted step or chunk call
    "train/device_wait",      # block_until_ready fence at the chunk edge
    "train/metrics_sync",     # readback of a logged step's metrics
    "train/ckpt_save",        # atomic checkpoint commit
    # serve/engine.py (+ StepSession)
    "serve/admit",            # admission: slot+pages grant, incl. prefill
    "serve/prefill",          # the jitted bucketed prefill call
    "serve/decode",           # one decode step over every active slot
    "serve/evict",            # instant: preempt evicted the batch
    # serve/router.py (instants on the virtual-clock event loop)
    "router/dispatch",        # primary copy dispatched to a replica
    "router/hedge",           # backup copy issued past the p95 threshold
    "router/timeout",         # attempt cancelled at its deadline
    "router/failover",        # unhealthy replica drained back to the queue
)


class NullTracer:
    """Tracing disabled: nothing is recorded and nothing fenced; ``span()``
    is only the profiler annotation, inactive unless a profile is taken."""

    __slots__ = ()
    enabled = False

    def span(self, name: str, cat: str = "", **args) -> TraceAnnotation:
        return TraceAnnotation(name)

    def instant(self, name: str, cat: str = "", **args) -> None:
        pass

    def counter(self, name: str, value: float) -> None:
        pass

    def export(self, path: str) -> None:
        pass


NULL = NullTracer()


def as_tracer(tracer) -> Any:
    """None -> the shared no-op tracer; anything else passes through."""
    return NULL if tracer is None else tracer


class _Span:
    """One live span: ``with tracer.span(...):`` emits an "X" event and
    holds the profiler annotation of the same name."""

    __slots__ = ("_tracer", "name", "cat", "args", "_start", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: Dict):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        tr = self._tracer
        tr._emit({"name": self.name, "cat": self.cat, "ph": "X",
                  "ts": (self._start - tr._t0) / 1e3,
                  "dur": (end - self._start) / 1e3,
                  "pid": tr.pid, "tid": tr.tid, "args": self.args})
        return False


class Tracer:
    """Ring-buffered span recorder with Chrome-trace JSON export.

    * ``span(name, **args)`` — a context manager; nesting is by lexical
      containment (the Chrome "X" complete-event model: a viewer stacks
      spans whose intervals nest on one track).
    * ``instant(name, **args)`` — a zero-duration marker ("i" event).
    * ``counter(name, value)`` — a "C" counter sample.
    * ``export(path)`` / ``to_dict()`` — the ``{"traceEvents": [...]}``
      JSON object perfetto loads directly.

    Timestamps are microseconds since the tracer's construction
    (``time.perf_counter_ns`` deltas — monotonic, never wall-time
    subject to NTP steps). Capacity bounds memory: the oldest events
    drop and ``dropped`` counts them, so a long run degrades to "the
    recent past" instead of OOM.
    """

    enabled = True

    def __init__(self, capacity: int = 1 << 16, pid: int = 0, tid: int = 0):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1 (got {capacity})")
        self.capacity = int(capacity)
        self.pid = pid
        self.tid = tid
        self.events: Deque[Dict] = collections.deque(maxlen=self.capacity)
        self.dropped = 0
        self._t0 = time.perf_counter_ns()

    def __len__(self) -> int:
        return len(self.events)

    def _emit(self, ev: Dict) -> None:
        if len(self.events) == self.capacity:
            self.dropped += 1
        self.events.append(ev)

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    # -- recording ------------------------------------------------------------

    def span(self, name: str, cat: str = "", **args) -> _Span:
        return _Span(self, name, cat or name.split("/", 1)[0], args)

    def instant(self, name: str, cat: str = "", **args) -> None:
        self._emit({"name": name, "cat": cat or name.split("/", 1)[0],
                    "ph": "i", "ts": self._now_us(), "s": "t",
                    "pid": self.pid, "tid": self.tid, "args": args})

    def counter(self, name: str, value: float) -> None:
        self._emit({"name": name, "ph": "C", "ts": self._now_us(),
                    "pid": self.pid, "tid": self.tid,
                    "args": {"value": float(value)}})

    # -- export ---------------------------------------------------------------

    def to_dict(self) -> Dict:
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms",
                "otherData": {"dropped": self.dropped,
                              "clock": "perf_counter_ns",
                              "capacity": self.capacity}}

    def export(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
        return path


def load_trace(path: str) -> Dict:
    """Load + structurally validate a Chrome-trace JSON file.

    The round-trip check the tests and the CI sample-trace step use:
    the object form with a ``traceEvents`` list whose entries carry the
    required ``name``/``ph``/``ts`` keys.
    """
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict) or "traceEvents" not in data:
        raise ValueError(f"{path}: not a Chrome-trace JSON object "
                         "(missing 'traceEvents')")
    for i, ev in enumerate(data["traceEvents"]):
        for key in ("name", "ph", "ts"):
            if key not in ev:
                raise ValueError(f"{path}: traceEvents[{i}] missing {key!r}")
        if ev["ph"] == "X" and "dur" not in ev:
            raise ValueError(f"{path}: traceEvents[{i}] is a complete "
                             "event without 'dur'")
    return data


def span_tree(events: List[Dict]) -> List[Dict]:
    """Nest "X" events by interval containment (per pid/tid track).

    Returns the roots; each node gains a ``children`` list. Used by the
    round-trip tests to assert the recorded nesting is well-formed.
    """
    spans = [dict(e) for e in events if e.get("ph") == "X"]
    spans.sort(key=lambda e: (e.get("pid", 0), e.get("tid", 0),
                              e["ts"], -e["dur"]))
    roots: List[Dict] = []
    stack: List[Dict] = []
    for ev in spans:
        ev["children"] = []
        while stack and not (
                stack[-1].get("pid", 0) == ev.get("pid", 0)
                and stack[-1].get("tid", 0) == ev.get("tid", 0)
                and ev["ts"] + ev["dur"]
                <= stack[-1]["ts"] + stack[-1]["dur"] + 1e-6):
            stack.pop()
        (stack[-1]["children"] if stack else roots).append(ev)
        stack.append(ev)
    return roots
