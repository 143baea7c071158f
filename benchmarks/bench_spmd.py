"""SPMD execution engine vs the single-device simulated backend.

Measures wall-clock steps/s of the tiny-LM backup-worker rig for
W in {4, 8} workers, chunk_size in {1, 32}, and mesh_model in {1, 2},
on both execution backends: 'sim' (one device, workers as loop index)
and 'spmd' (the repro.distributed.spmd_engine — workers over a real
mesh 'data' axis with mesh_data = W, masked aggregation as an in-shard
backup_reduce + psum collective; docs/spmd.md). mesh_model = 2
additionally shards params / optimizer state / EMA over the mesh
'model' axis and computes each worker's gradient tensor-parallel
(explicit psums at the contracted dims) — the TP overhead relative to
the replicated mesh_model = 1 engine is the new quantity this benchmark
tracks.

The process forces 16 host platform devices (the (W=8, M=2) cell), so
on CPU hosts every "device" is a slice of the same machine and the
ratios reported here measure the ENGINE'S overhead (shard_map
partitioning, the collectives, the interpret-mode Pallas reduce), not a
speedup — the win appears on real accelerators where the per-worker
gradients (and, under TP, each gradient's matmuls) genuinely
parallelize. Tracking the overhead ratio per commit is the point: it is
the price of mesh execution at a given (W, K, M), and regressions here
are regressions on real hardware too.

Each cell also reports a BYTES-MOVED axis: per-step collective wire
traffic parsed from the optimized HLO (launch.dryrun.parse_collectives,
while-loop bodies multiplied by trip count) — after the fused bucketed
reduce-then-psum rework one psum per bucket carries gradient plus
monitoring scalars, so the axis makes the collective-count win (3 ->
1 per step at bucket_size=0) directly visible next to the wall-clock.

Writes experiments/bench/BENCH_spmd.json and mirrors the headline
summary to the repo-root BENCH_spmd.json.
"""
from __future__ import annotations

import os

# must precede ANY jax import in this process (common.py imports jax).
# The (W=8, mesh_model=2) cell needs 16 devices: raise any pre-existing
# forced count below that (e.g. the 8 every doc example exports) instead
# of inheriting it and crashing mid-run at the m=2 cells.
import re as _re

_FORCED = "--xla_force_host_platform_device_count"
_flags = os.environ.get("XLA_FLAGS", "")
_m = _re.search(_re.escape(_FORCED) + r"=(\d+)", _flags)
if _m is None:
    os.environ["XLA_FLAGS"] = (_flags + f" {_FORCED}=16").strip()
elif int(_m.group(1)) < 16:
    os.environ["XLA_FLAGS"] = _flags.replace(_m.group(0), f"{_FORCED}=16")

import argparse
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))

from common import write_bench

WORKER_COUNTS = (4, 8)
CHUNK_SIZES = (1, 32)
MESH_MODELS = (1, 2)


def build_trainer(backend: str, workers: int, chunk_size: int,
                  mesh_model: int = 1, tracer=None, metrics=None):
    from repro import configs
    from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                    ExecutionConfig, OptimizerConfig,
                                    ShapeConfig, TrainConfig, replace)
    from repro.core.straggler import Uniform
    from repro.train.loop import Trainer

    # tiny model, small shape: the measurement isolates the execution
    # machinery (dispatch, partitioning, collectives), not model FLOPs.
    # Dims are chosen divisible by mesh_model=2 so the TP cells shard.
    model = replace(configs.get_smoke_config("qwen3-0.6b"), num_layers=1,
                    d_model=32, num_heads=2, num_kv_heads=2, head_dim=16,
                    d_ff=64, vocab_size=64, vocab_pad_multiple=16)
    cfg = TrainConfig(
        model=model,
        shape=ShapeConfig("bench", 16, 2 * workers, "train"),
        aggregation=AggregationConfig(strategy="backup",
                                      num_workers=workers - 1,
                                      backup_workers=1),
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.02,
                                  scale_lr_with_workers=False,
                                  ema_decay=0.999),
        checkpoint=CheckpointConfig(every_steps=0),
        execution=ExecutionConfig(backend=backend, mesh_data=workers,
                                  mesh_model=mesh_model),
        log_every=1, chunk_size=chunk_size)
    tr = Trainer(cfg, latency=Uniform(1.0, 2.0), tracer=tracer,
                 metrics=metrics)
    tr.init_state()
    return tr


def collective_bytes_per_step(tr) -> dict:
    """The bytes-moved axis: lower the trainer's installed step (the
    chunked scan when chunk_size > 1), parse the optimized HLO with
    ``launch.dryrun.parse_collectives`` (while-loop bodies multiplied by
    trip count), and report per-STEP collective traffic. 'sim' cells are
    single-device and report zeros — the axis prices exactly what the
    mesh engine puts on the wire (one fused psum per bucket after the
    bucketed reduce-then-psum rework; docs/spmd.md)."""
    import jax.numpy as jnp

    from repro.launch.dryrun import parse_collectives

    cfg = tr.cfg
    K = cfg.chunk_size
    B, S = cfg.shape.global_batch, cfg.shape.seq_len
    W = cfg.aggregation.total_workers
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}
    if K > 1:
        stack = {k: jnp.zeros((K,) + v.shape, v.dtype)
                 for k, v in batch.items()}
        lowered = tr.chunk_step.lower(
            tr.params, tr.opt_state, tr.ema, jnp.int32(0), stack,
            jnp.ones((K, W), jnp.float32))
    else:
        lowered = tr.train_step.lower(
            tr.params, tr.opt_state, tr.ema, jnp.int32(0), batch,
            jnp.ones((W,), jnp.float32))
    coll = parse_collectives(lowered.compile().as_text())
    return {"collective_bytes_per_step": coll["total_wire_bytes"] / K,
            "collective_ops_per_step": coll["num_ops"] / K}


def measure_all(specs, steps: int, reps: int = 5, tracer=None, metrics=None):
    """Build+compile every config first, then interleave the timed reps
    so CPU thermal drift doesn't systematically penalize whichever
    config is measured last. Best-of-5 per config: the fast chunk=1
    cells step in ~3ms, so best-of-3 still carries visible scheduler
    noise into the ratios. The bytes-moved axis is read from each
    trainer's lowered HLO after the timed reps (untimed)."""
    trainers = []
    for backend, workers, chunk, mesh_model in specs:
        tr = build_trainer(backend, workers, chunk, mesh_model,
                           tracer=tracer, metrics=metrics)
        tr.run(max(chunk, 8))                      # compile + warm caches
        trainers.append(tr)
    best = [None] * len(specs)
    for _ in range(reps):
        for i, tr in enumerate(trainers):
            t0 = time.perf_counter()
            tr.run(steps)
            dt = time.perf_counter() - t0
            best[i] = dt if best[i] is None or dt < best[i] else best[i]
    return [{"backend": b, "workers": w, "chunk_size": c, "mesh_model": m,
             "steps": steps, "wall_s": wall, "steps_per_s": steps / wall,
             **collective_bytes_per_step(tr)}
            for (b, w, c, m), wall, tr in zip(specs, best, trainers)]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer timed steps (CI)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record host-side spans across every measured "
                         "trainer and export a Chrome trace here (adds "
                         "dispatch fences — numbers will be slower)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="dump the unified metrics registry as JSONL here")
    ap.add_argument("--platform", default=None, choices=("cpu", "gpu"),
                    help="pin the jax platform and apply its XLA flag "
                         "recipe (gpu: the latency-hiding flags the "
                         "bucketed psum overlap is shaped for)")
    args = ap.parse_args(argv)
    if args.platform:
        from repro.launch import mesh as mesh_lib
        added = mesh_lib.set_platform(args.platform)
        if added:
            print(f"[bench_spmd] XLA flags: {' '.join(added)}")
    tracer = metrics = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()
    if args.metrics:
        from repro.obs import MetricsRegistry
        metrics = MetricsRegistry()

    steps = 32 if args.quick else 96
    specs = [("sim", w, c, 1) for w in WORKER_COUNTS for c in CHUNK_SIZES]
    specs += [("spmd", w, c, m) for w in WORKER_COUNTS for c in CHUNK_SIZES
              for m in MESH_MODELS]
    results = measure_all(specs, steps, tracer=tracer, metrics=metrics)

    def rate(backend, workers, chunk, mesh_model):
        return next(r["steps_per_s"] for r in results
                    if r["backend"] == backend and r["workers"] == workers
                    and r["chunk_size"] == chunk
                    and r["mesh_model"] == mesh_model)

    # spmd/sim per cell: < 1 on forced CPU devices (engine overhead),
    # the quantity to keep from regressing; the m2 cells price the
    # tensor-parallel collectives on top of the worker-mesh machinery
    ratios = {f"spmd_vs_sim_w{w}_chunk{c}_m{m}":
              rate("spmd", w, c, m) / rate("sim", w, c, 1)
              for w in WORKER_COUNTS for c in CHUNK_SIZES
              for m in MESH_MODELS}
    # bytes-moved axis: per-step collective wire traffic of each spmd
    # cell (sim cells are single-device, identically zero)
    bytes_moved = {
        f"spmd_bytes_per_step_w{r['workers']}_chunk{r['chunk_size']}"
        f"_m{r['mesh_model']}": r["collective_bytes_per_step"]
        for r in results if r["backend"] == "spmd"}
    payload = {
        "bench": "spmd",
        "model": "qwen3-0.6b tiny (1L, d32)",
        "devices_forced": 16,
        "mesh_models": list(MESH_MODELS),
        "steps": steps,
        "results": results,
        **ratios,
        **bytes_moved,
    }
    path = write_bench("BENCH_spmd", payload,
                       mirror={"bench": "spmd", **ratios, **bytes_moved})
    for r in results:
        print(f"backend={r['backend']:<5} W={r['workers']} "
              f"chunk={r['chunk_size']:>3} m={r['mesh_model']} "
              f"{r['steps_per_s']:8.1f} steps/s "
              f"{r['collective_bytes_per_step'] / 1024:8.1f} KiB/step "
              f"({r['collective_ops_per_step']:.0f} colls)")
    for k, v in ratios.items():
        print(f"{k}: {v:.3f}")
    if tracer is not None:
        tracer.export(args.trace)
        print(f"[bench_spmd] trace: {args.trace} ({len(tracer)} events)")
    if metrics is not None:
        metrics.dump_jsonl(args.metrics)
        print(f"[bench_spmd] metrics: {args.metrics} "
              f"({len(metrics)} series)")
    print(f"-> {path} (+ root BENCH_spmd.json)")
    return payload


def run(quick: bool = True):
    """benchmarks/run.py harness contract: (name, us_per_call, derived).

    On a CPU host this executes in a fresh subprocess: the forced host
    device count must be set before jax initializes, which the harness
    process already did. On an accelerator the harness process holds the
    chips, and a child could not take them, so the bench runs in-process
    on the real devices, or fails when there are too few. Trace /
    metrics / platform requests arrive through the
    ``REPRO_BENCH_TRACE`` / ``REPRO_BENCH_METRICS`` /
    ``REPRO_BENCH_PLATFORM`` env vars (the ``run(quick)`` signature is
    fixed by the harness), forwarded as the bench's own CLI flags.
    """
    import json

    import jax
    flags = ["--quick"] if quick else []
    for var, flag in (("REPRO_BENCH_TRACE", "--trace"),
                      ("REPRO_BENCH_METRICS", "--metrics"),
                      ("REPRO_BENCH_PLATFORM", "--platform")):
        val = os.environ.get(var)
        if val:
            flags += [flag, val]
    backend = jax.default_backend()
    if backend != "cpu":
        need = max(WORKER_COUNTS) * max(MESH_MODELS)
        have = jax.device_count()
        if have < need:
            raise RuntimeError(
                f"bench_spmd needs {need} {backend} devices for its "
                f"(W={max(WORKER_COUNTS)}, mesh_model={max(MESH_MODELS)}) "
                f"cell; this host has {have}")
        payload = main(flags)
    else:
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)    # let the module force its own devices
        subprocess.run([sys.executable, os.path.abspath(__file__)] + flags,
                       check=True, env=env,
                       cwd=os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(os.path.dirname(__file__), "..",
                               "experiments", "bench",
                               "BENCH_spmd.json")) as f:
            payload = json.load(f)
    rows = [(f"spmd.{r['backend']}_w{r['workers']}_chunk{r['chunk_size']}"
             f"_m{r['mesh_model']}",
             1e6 / r["steps_per_s"], f"{r['steps_per_s']:.1f}steps/s")
            for r in payload["results"]]
    rows += [(f"spmd.{k}", 0.0, f"{v:.3f}x")
             for k, v in payload.items() if k.startswith("spmd_vs_sim")]
    return rows


if __name__ == "__main__":
    main()
