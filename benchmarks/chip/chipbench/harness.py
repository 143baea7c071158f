"""One run of one cell: set-up, the timed window, the check, the result.

Set-up builds the program's ``Trainer`` through the training CLI's own
``build_config``, with the CLI's straggler latency model and no
checkpoints, and hands it:

* the weights, optimizer state and EMA, made on the device in one
  jitted call from the seed (``model_spec.init_fn``, the program's
  optimizer and EMA initializers);
* the traffic, in the slot of its data pipeline (``feed.Feed``).

It then drives that same trainer through its first three steps with
``Trainer.run``, the window's own call, reading after the first step the
gradient the optimizer got (from its RMSProp state: ms = (1 - decay)
g^2) and after the third the change of the weights and of the EMA. The
first step compiles the step program, or loads it from the persistent
cache: it is the warm-up, and the window sees no new shape.

The program's own ``Trainer.init_state`` and its input pipeline
(``SyntheticLMPipeline``) are not run: neither is on the timed path, and
``setup_s`` does not see them.

The window calls ``Trainer.run`` in blocks of ``steps_per_block`` steps
until ``--seconds`` have passed, and ends on ``block_until_ready`` of
the trained state. Python's collector is run and its survivors frozen
before the window opens, so that the set-up's objects are not walked
again inside it; the time of each block and of any collection in the
window goes to standard error. Once it has closed, the peak memory is read, the
program's state is freed, and the plain reference (``reference.run``)
repeats the three steps from the seed; ``check`` compares the two.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
import types
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from chipbench import check, feed, flops, model_spec, peaks, spec
from chipbench import trace as trace_lib

SETUP_STEPS = 3
CACHE_DIR = os.path.join(spec.BENCH_DIR, ".jax_cache")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def use_compile_cache(path: str = CACHE_DIR) -> None:
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, of the benchmark's own (entries that another tool left
    behind cannot break it), every program in it, so a second run
    compiles nothing."""
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


class _CompileCounter:
    """Counts programs lowered (each new jit specialization, whether the
    persistent cache then holds it or not)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


_COUNTER: Optional[_CompileCounter] = None


class _GcClock:
    """Seconds Python's collector runs while it is installed in
    ``gc.callbacks``."""

    def __init__(self):
        self.seconds, self.count, self._start = 0.0, 0, None

    def __call__(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        elif self._start is not None:
            self.seconds += time.perf_counter() - self._start
            self.count += 1
            self._start = None


def _compile_counter() -> _CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = _CompileCounter()
    return _COUNTER


# ---------------------------------------------------------------------------
# Building the system under test
# ---------------------------------------------------------------------------


def _program_config(cell: spec.Cell, traffic: feed.Traffic, seed: int):
    """The TrainConfig the training CLI builds for this cell, with the
    model's sizes taken from the configuration file by its architecture
    (``archs/<model_type>.py``: ``program_model``)."""
    from repro.launch.train import build_config

    run = cell.config["run"]
    opt = run["optimizer"]
    spmd = run["execution"] == "spmd"
    args = types.SimpleNamespace(
        arch=run["arch"], smoke=False, seq=traffic.seq_len,
        batch_per_worker=traffic.rows_per_worker, strategy=run["strategy"],
        workers=traffic.workers, backups=traffic.backups, deadline=None,
        softsync_c=None, dynamic_window=None, latency_source="sim",
        optimizer=opt["name"], lr=float(opt["lr"]),
        ckpt=os.path.join(tempfile.gettempdir(), "chipbench-no-checkpoint"),
        ckpt_every=0, execution=run["execution"],
        mesh_data=run["mesh_data"] if spmd else None, mesh_model=None,
        grad_batch=None, bucket_size=None, seed=seed, steps=0,
        log_every=1 << 30, chunk_size=run["chunk_size"],
        straggler_backend=run["straggler_backend"], prefetch_depth=1,
        faults=None, fault_seed=0, supervise=False, max_restarts=0)
    cfg = build_config(args)
    s = model_spec.sizes(cell.config, cell.bench_dir)
    try:
        model = spec.arch(s.model_type).program_model(cfg.model, s)
    except ValueError as e:
        raise ValueError(f"the program's {run['arch']} is not the model of "
                         f"{cell.config_name}: {e}") from None
    o = cfg.optimizer
    stated = (opt["name"], float(opt["decay"]), float(opt["momentum"]),
              float(opt["eps"]), float(opt["ema_decay"]),
              bool(opt["scale_lr_with_workers"]))
    built = (o.name, o.decay, o.momentum, o.eps, o.ema_decay,
             o.scale_lr_with_workers)
    if stated != built or o.warmup_steps or o.steps_per_epoch \
            or o.linear_anneal_steps or o.clip_global_norm:
        raise ValueError(f"the CLI builds optimizer {o}, the configuration "
                         f"states {opt}")
    return dataclasses.replace(cfg, model=model)


class _Recorder:
    """Wraps the straggler simulator's ``next_event``: keeps the arrival
    times of the first steps and counts the workers selected."""

    def __init__(self, sim, keep: int):
        self._next = sim.next_event
        self.keep = keep
        self.arrivals: List[np.ndarray] = []
        self.selected = 0
        sim.next_event = self

    def __call__(self):
        ev = self._next()
        if len(self.arrivals) < self.keep:
            self.arrivals.append(np.array(ev.arrivals, np.float64))
        self.selected += int(np.sum(ev.mask))
        return ev


def _rms_grad_norms(ms, decay: float):
    """Per-tensor norms and sketches of |gradient| that RMSProp saw on
    its first step: from zero state, ms = (1 - decay) * g^2."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference
    return reference.norms(jax.tree_util.tree_map(
        lambda m: jnp.sqrt(m / (1.0 - decay)), ms))


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SetUp:
    """The trainer after its first steps, and what they read."""
    tr: Any
    rec: _Recorder
    traffic: feed.Traffic
    sizes: Any                   # archs/<model_type>.py: sizes()
    key: Any
    prog: Dict[str, Any]
    devices: List[Any]
    phases: Dict[str, float]     # seconds each part of set-up took


@dataclasses.dataclass
class Run:
    result: Dict[str, Any]      # the result line
    report: List[str]           # what goes to standard error


def _finite(x: float) -> float:
    """JSON has no infinity: a gap that is not finite prints as 1e300."""
    return x if math.isfinite(x) else 1e300


def set_up(cell: spec.Cell, seed: int, *, require_chip: bool = True,
           mutate: Optional[Callable] = None) -> SetUp:
    """Build the trainer, make its state from the seed, run its first
    steps through ``Trainer.run`` and read them. ``mutate(trainer)``
    (tests only) breaks the timed path once it is built."""
    marks = [("start", time.perf_counter())]
    import jax
    from repro.core import ema as ema_lib
    from repro.core.straggler import PaperCalibrated
    from repro.train.loop import Trainer
    from chipbench import reference

    marks.append(("program imports", time.perf_counter()))
    devices = require_chips(cell.chips) if require_chip else jax.devices()
    traffic = feed.traffic(cell.traffic)
    sizes = model_spec.sizes(cell.config, cell.bench_dir)
    cfg = _program_config(cell, traffic, feed.derived_seed(seed, 1))
    key = jax.random.PRNGKey(feed.derived_seed(seed, 2, bits=32))
    decay = float(cell.config["run"]["optimizer"]["decay"])

    tr = Trainer(cfg, latency=PaperCalibrated())
    init = model_spec.init_fn(sizes)
    if model_spec.tree_signature(jax.eval_shape(tr.model.init, key)) != \
            model_spec.tree_signature(jax.eval_shape(init, key)):
        raise ValueError("the benchmark's parameter layout is not the "
                         "program's")
    tr.pipeline = feed.Feed(traffic, sizes.vocab, seed)
    rec = _Recorder(tr.sim, SETUP_STEPS)
    # the engine's own shardings on the mesh (replicated without a
    # 'model' axis); one device otherwise
    state_sharding = getattr(tr, "_state_shardings", None)

    def make_state(k):
        p = init(k)
        return p, tr.optimizer.init(p), ema_lib.init(p)

    marks.append(("trainer", time.perf_counter()))
    tr.params, tr.opt_state, tr.ema = jax.jit(
        make_state, out_shardings=state_sharding)(key)
    jax.block_until_ready(tr.params)
    marks.append(("state", time.perf_counter()))
    if mutate is not None:
        mutate(tr)

    losses = []
    for i in range(SETUP_STEPS):
        tr.run(1)
        losses.append(float(tr.metrics[-1]["loss"]))
        if i == 0:
            marks.append(("first_step", time.perf_counter()))
            grad = _rms_grad_norms(tr.opt_state["ms"], decay)
    marks.append(("steps", time.perf_counter()))
    theta0 = jax.jit(init, out_shardings=(
        state_sharding and state_sharding[0]))(key)
    prog = reference.readings(losses, grad,
                              reference.change_norms(tr.params, theta0),
                              reference.change_norms(tr.ema, theta0))
    del theta0
    jax.block_until_ready((tr.params, tr.opt_state, tr.ema))
    marks.append(("readings", time.perf_counter()))
    phases = {name: t - marks[i][1]
              for i, (name, t) in enumerate(marks[1:])}
    return SetUp(tr=tr, rec=rec, traffic=traffic, sizes=sizes, key=key,
                 prog=prog, devices=list(devices), phases=phases)


def reference_readings(cell: spec.Cell, s: SetUp, seed: int,
                       arrivals: List[np.ndarray], **kw) -> Dict[str, Any]:
    """The plain reference's readings of the same first steps."""
    from chipbench import reference
    data = feed.Feed(s.traffic, s.sizes.vocab, seed)
    return reference.run(cell.config, s.traffic, s.key,
                         [data.batch(i) for i in range(SETUP_STEPS)],
                         arrivals, **kw)


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
             t0: float, require_chip: bool = True,
             mutate: Optional[Callable] = None,
             start_phases: Optional[Dict[str, float]] = None) -> Run:
    """One run: set-up, the window, then the check. ``start_phases``
    names what the process did before ``run_cell``, for the report."""
    import jax

    counter = _compile_counter()
    s = set_up(cell, seed, require_chip=require_chip, mutate=mutate)
    tr, rec, traffic, devices = s.tr, s.rec, s.traffic, s.devices

    # ---- the window ------------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    if traced:
        jax.profiler.start_trace(trace_dir)
    step0, sel0, compiles0 = tr.step, rec.selected, counter.count
    blocks, block_s = [], []
    gc.collect()
    gc.freeze()
    gc_clock = _GcClock()
    gc.callbacks.append(gc_clock)
    t_w0 = t_b = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench/window"):
        while True:
            with jax.profiler.TraceAnnotation("bench/trainer_run"):
                tr.run(traffic.steps_per_block)
            blocks.append(float(tr.metrics[-1]["loss"]))
            now = time.perf_counter()
            block_s.append(now - t_b)
            t_b = now
            if now - t_w0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("bench/block_until_ready"):
            jax.block_until_ready((tr.params, tr.opt_state, tr.ema))
    t_w1 = time.perf_counter()
    gc.callbacks.remove(gc_clock)
    gc.unfreeze()
    if traced:
        jax.profiler.stop_trace()
    window_compiles = counter.count - compiles0
    steps = tr.step - step0
    useful_tokens = (rec.selected - sel0) * traffic.tokens_per_worker
    setup_s = t_w0 - t0
    wall = t_w1 - t_w0

    mem = [d.memory_stats() or {} for d in devices[:cell.chips]]
    peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    arrivals = list(rec.arrivals)
    s.tr = s.rec = tr = rec = None
    gc.collect()

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    phases = dict(start_phases or {}, **s.phases)
    report = [f"setup_s {setup_s:.3f}: interpreter and imports "
              f"{setup_s - sum(phases.values()):.3f}, "
              + ", ".join(f"{k} {v:.3f}" for k, v in phases.items()),
              f"window {wall:.3f} s, {steps} steps, "
              f"{useful_tokens} useful tokens, {window_compiles} programs "
              f"lowered in the window",
              f"blocks of {traffic.steps_per_block} steps: {len(block_s)}, "
              f"median {statistics.median(block_s):.3f} s, slowest "
              f"{max(block_s):.3f} s; {gc_clock.count} collections in the "
              f"window, {gc_clock.seconds:.3f} s"]
    breakdown = None
    if traced:
        tr_data = trace_lib.from_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = trace_lib.mean(trace_lib.busy_s(tr_data))
        device["window_s"] = trace_lib.window_s(tr_data)
        ctx = types.SimpleNamespace(
            trace=tr_data, trace_lib=trace_lib, flops=flops, sizes=s.sizes,
            traffic=traffic, chips=cell.chips, steps=steps,
            useful_tokens=useful_tokens,
            mesh_data=int(cell.config["run"]["mesh_data"]),
            peaks=peaks.peaks(devices[0].device_kind))
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        breakdown = trace_lib.breakdown(tr_data)
    else:
        values = {"train_tokens_per_s": useful_tokens / wall,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}

    ref = reference_readings(cell, s, seed, arrivals)
    numbers = check.gaps(s.prog, ref)
    limits = cell.limits["limits"]
    checks = check.judge(numbers, limits)
    correct = (check.passed(checks) and window_compiles == 0
               and all(map(math.isfinite, blocks)))
    if window_compiles:
        report.append("not correct: programs were lowered inside the window")
    for name in check.NUMBERS:
        lim = limits.get(name)
        report.append(f"{name} {numbers[name]:.6g} limit "
                      + ("(not compared)" if lim is None else f"{lim:.6g}"))
    result = {"correct": bool(correct), "attempted": steps,
              "failed": sum(1 for b in blocks if not math.isfinite(b)),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window_compiles"] = window_compiles
    result["checks"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                        for k, v in checks.items()}
    return Run(result=result, report=report)


def main(argv=None, t0: Optional[float] = None) -> int:
    import argparse
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    t1 = time.perf_counter()
    try:
        require_chips(cell.chips)
    except NoChip as e:
        print(f"chipbench: {e}; nothing run", file=sys.stderr)
        return 2
    use_compile_cache()
    start = {"device start": time.perf_counter() - t1}
    run = run_cell(cell, args.seed, args.seconds, bool(args.trace), t0=t0,
                   start_phases=start)
    for line in run.report:
        print(line, file=sys.stderr)
    print(json.dumps(run.result))
    return 0
