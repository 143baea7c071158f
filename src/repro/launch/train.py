"""Training launcher CLI — one entry point for every coordination regime.

    python -m repro.launch.train --arch qwen3-0.6b --smoke --steps 50 \
        --strategy backup --workers 6 --backups 2 [--resume]
    python -m repro.launch.train --arch qwen3-0.6b --smoke --steps 50 \
        --strategy async --workers 6
    python -m repro.launch.train --arch qwen3-0.6b --smoke --steps 50 \
        --strategy softsync --workers 6 --softsync-c 2
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m repro.launch.train --arch qwen3-0.6b --smoke --steps 50 \
        --strategy backup --workers 6 --backups 2 \
        --execution spmd --mesh-data 8 --chunk-size 8

--smoke uses the reduced per-arch config (CPU-runnable); without it the
full published config is built (TPU-scale: ``chip_smoke.py`` at the repo
root drives it on one v5e chip). Everything routes through ``repro.train.loop.run_experiment``:
mask strategies (backup/full_sync/timeout/dynamic_backup) drive the
straggler simulator and the masked SPMD step; event strategies
(async/softsync) drive the discrete-event parameter server — both with
the paper's lr rule, EMA, atomic checkpoints, and the unified metrics
schema (docs/api.md).

Chaos engineering (docs/robustness.md): ``--faults`` attaches a seeded
fault plan, ``--supervise`` routes the run through the recovery
supervisor so injected crashes/preemptions restore-and-continue:

    python -m repro.launch.train --arch qwen3-0.6b --smoke --steps 50 \
        --strategy backup --workers 6 --backups 2 \
        --faults 'crash@10:w2,slow@5:w0,preempt@30' --supervise
"""
from __future__ import annotations

import argparse
import os

from repro import configs
from repro.configs.base import (AggregationConfig, CheckpointConfig,
                                ExecutionConfig, FaultConfig, OptimizerConfig,
                                ShapeConfig, TrainConfig)
from repro.core.straggler import PaperCalibrated
from repro.launch.mesh import init_compile_cache
from repro.train.loop import run_experiment

MASK_STRATEGIES = ("backup", "full_sync", "timeout", "dynamic_backup")
EVENT_STRATEGIES = ("async", "softsync")


def _resolved_workers(args):
    """(backups, total launched) after defaults — the ONE definition both
    build_config and the arg validation use."""
    with_backups = args.strategy in ("backup", "dynamic_backup")
    backups = args.backups if args.backups is not None else (
        2 if with_backups else 0)
    total = args.workers + (backups if with_backups else 0)
    return backups, total


def build_config(args) -> TrainConfig:
    """args -> TrainConfig, with strategy-specific arg validation."""
    model_cfg = (configs.get_smoke_config(args.arch) if args.smoke
                 else configs.get_config(args.arch))
    backups, total = _resolved_workers(args)
    deadline = args.deadline if args.deadline is not None else 2.0
    softsync_c = args.softsync_c if args.softsync_c is not None else 2
    return TrainConfig(
        model=model_cfg,
        shape=ShapeConfig("cli", args.seq, args.batch_per_worker * total,
                          "train"),
        aggregation=AggregationConfig(strategy=args.strategy,
                                      num_workers=args.workers,
                                      backup_workers=backups,
                                      deadline_s=deadline,
                                      softsync_c=softsync_c,
                                      dynamic_window=(args.dynamic_window
                                                      or 32),
                                      latency_source=args.latency_source),
        optimizer=OptimizerConfig(name=args.optimizer,
                                  learning_rate=args.lr,
                                  scale_lr_with_workers=True,
                                  ema_decay=0.999),
        checkpoint=CheckpointConfig(directory=args.ckpt,
                                    every_steps=args.ckpt_every),
        execution=ExecutionConfig(backend=args.execution,
                                  mesh_data=args.mesh_data or 1,
                                  mesh_model=args.mesh_model or 1,
                                  grad_batch=args.grad_batch or 0,
                                  bucket_size=args.bucket_size or 0),
        seed=args.seed, total_steps=args.steps, log_every=args.log_every,
        chunk_size=args.chunk_size,
        straggler_backend=args.straggler_backend,
        prefetch_depth=args.prefetch_depth,
        faults=FaultConfig(spec=args.faults or "", seed=args.fault_seed,
                           supervise=args.supervise,
                           max_restarts=args.max_restarts))


def _validate(ap: argparse.ArgumentParser, args) -> None:
    """Reject argument combinations that would silently do nothing."""
    if args.backups is not None and args.strategy not in ("backup",
                                                          "dynamic_backup"):
        ap.error(f"--backups only applies to --strategy backup or "
                 f"dynamic_backup (got --strategy {args.strategy})")
    if args.dynamic_window is not None and args.strategy != "dynamic_backup":
        ap.error(f"--dynamic-window only applies to --strategy "
                 f"dynamic_backup (got --strategy {args.strategy})")
    if args.strategy == "dynamic_backup" and args.straggler_backend != "host":
        ap.error("--strategy dynamic_backup selects on the host (stateful "
                 "adaptation): --straggler-backend must be host")
    if args.latency_source != "sim" and args.strategy != "dynamic_backup":
        ap.error(f"--latency-source measured only applies to --strategy "
                 f"dynamic_backup (got --strategy {args.strategy})")
    for flag, value in (("--trace", args.trace),
                        ("--metrics", args.metrics)):
        if value is not None:
            parent = os.path.dirname(os.path.abspath(value))
            if not os.path.isdir(parent):
                ap.error(f"{flag} {value}: directory {parent} does not exist")
    if args.faults and args.straggler_backend != "host":
        ap.error("--faults composes with host-planned arrivals only: "
                 "--straggler-backend must be host")
    if args.deadline is not None and args.strategy != "timeout":
        ap.error(f"--deadline only applies to --strategy timeout "
                 f"(got --strategy {args.strategy})")
    if args.softsync_c is not None and args.strategy != "softsync":
        ap.error(f"--softsync-c only applies to --strategy softsync "
                 f"(got --strategy {args.strategy})")
    if args.strategy in EVENT_STRATEGIES and args.straggler_backend != "host":
        ap.error(f"--straggler-backend device only applies to mask "
                 f"strategies (got --strategy {args.strategy})")
    for flag, value in (("--mesh-data", args.mesh_data),
                        ("--mesh-model", args.mesh_model),
                        ("--grad-batch", args.grad_batch),
                        ("--bucket-size", args.bucket_size)):
        if value is not None and args.execution != "spmd":
            ap.error(f"{flag} only applies to --execution spmd")
    if args.execution == "spmd":
        if args.strategy in EVENT_STRATEGIES:
            ap.error(f"--execution spmd only applies to mask strategies "
                     f"(got --strategy {args.strategy})")
        if args.straggler_backend != "host":
            ap.error("--execution spmd consumes host-planned masks: "
                     "--straggler-backend must be host")
        _, total = _resolved_workers(args)
        if total % (args.mesh_data or 1):
            ap.error(f"total workers ({total}) must be divisible by "
                     f"--mesh-data ({args.mesh_data})")
        if args.grad_batch is not None:
            from repro.distributed.spmd_engine import validate_grad_batch
            try:
                validate_grad_batch(args.grad_batch,
                                    total // (args.mesh_data or 1))
            except ValueError as e:
                ap.error(f"--grad-batch: {e}")


def main(argv=None):
    """Parse ``argv``, run the experiment, print its report and return
    the :class:`~repro.train.loop.TrainResult`."""
    init_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=configs.list_archs(),
                    default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50,
                    help="training steps (PS updates for async/softsync)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch-per-worker", type=int, default=4)
    ap.add_argument("--strategy", default="backup",
                    choices=list(MASK_STRATEGIES) + list(EVENT_STRATEGIES))
    ap.add_argument("--workers", type=int, default=6)
    ap.add_argument("--backups", type=int, default=None,
                    help="backup workers b (backup strategy only; default 2)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="aggregation deadline s (timeout strategy only; "
                         "default 2.0)")
    ap.add_argument("--softsync-c", type=int, default=None,
                    help="gradients averaged per update (softsync only; "
                         "default 2)")
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--optimizer", default="rmsprop_momentum")
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10,
                    help="record and print the metrics of every N-th step "
                         "(and of the last)")
    ap.add_argument("--chunk-size", type=int, default=1,
                    help="iterations fused per device dispatch — SPMD steps "
                         "for mask strategies, PS updates for event "
                         "strategies (1 = legacy per-step/per-arrival loop)")
    ap.add_argument("--straggler-backend", choices=["host", "device"],
                    default="host",
                    help="'device' samples arrivals/batches inside the scan")
    ap.add_argument("--execution", choices=["sim", "spmd"], default="sim",
                    help="'spmd' runs the workers over a real device mesh "
                         "(repro.distributed.spmd_engine, docs/spmd.md); "
                         "'sim' is the single-device simulated backend")
    ap.add_argument("--mesh-data", type=int, default=None,
                    help="devices on the mesh 'data' (worker) axis "
                         "(spmd only; total workers must divide evenly)")
    ap.add_argument("--mesh-model", type=int, default=None,
                    help="devices on the mesh 'model' axis (spmd only): "
                         "shards params/opt state/EMA and computes each "
                         "worker's gradient tensor-parallel (docs/spmd.md); "
                         "model dims must divide or the axis is carried "
                         "replicated")
    ap.add_argument("--grad-batch", type=int, default=None,
                    help="per-shard worker-gradient batching (spmd only): "
                         "0 = vmap all local workers (fast path), 1 = "
                         "sequential lax.map (lowest activation memory), "
                         "k = microbatches of k workers (must divide "
                         "total workers / mesh-data)")
    ap.add_argument("--bucket-size", type=int, default=None,
                    help="lanes of the flattened gradient per collective "
                         "in the fused bucketed reduce-then-psum (spmd "
                         "only; 0 = one psum carries gradient + metrics, "
                         "docs/spmd.md)")
    ap.add_argument("--platform", choices=["cpu", "gpu", "tpu"],
                    default=None,
                    help="pin the jax platform and apply its XLA flag "
                         "recipe before backend init (launch.mesh."
                         "set_platform; on gpu this enables async "
                         "collectives + the latency-hiding scheduler the "
                         "bucketed reduce-then-psum overlaps under)")
    ap.add_argument("--prefetch-depth", type=int, default=1,
                    help="chunks speculatively built ahead of the device "
                         "dispatch (chunked loop; 1 = double buffering)")
    ap.add_argument("--dynamic-window", type=int, default=None,
                    help="sliding window of steps the adaptive cutoff is "
                         "estimated over (dynamic_backup only; default 32)")
    ap.add_argument("--faults", default=None,
                    help="chaos plan spec, e.g. 'crash@10:w2,slow@5:w0,"
                         "ckpt_io@20,preempt@30' or 'crash=2,slow=3' for "
                         "seeded-random placement (docs/robustness.md)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for random fault placement and the "
                         "deterministic recovery log")
    ap.add_argument("--supervise", action="store_true",
                    help="run under the recovery supervisor: injected/real "
                         "crashes restore the last good checkpoint and "
                         "continue (repro.train.supervisor)")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="supervisor restart budget before giving up")
    ap.add_argument("--latency-source", choices=["sim", "measured"],
                    default="sim",
                    help="where dynamic_backup's adaptation window comes "
                         "from: the straggler simulator's arrival model, or "
                         "fenced wall-clock per-worker step times measured "
                         "on the real mesh (docs/observability.md)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record host-side spans and export Chrome-trace "
                         "JSON here (load at ui.perfetto.dev); enables "
                         "block_until_ready fences at chunk edges")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="dump the unified metrics registry as JSONL here "
                         "(one object per metric; docs/observability.md)")
    args = ap.parse_args(argv)
    _validate(ap, args)

    if args.platform:
        from repro.launch import mesh as mesh_lib
        added = mesh_lib.set_platform(args.platform)
        if added:
            print(f"[train] XLA latency-hiding flags: {' '.join(added)}")
    cfg = build_config(args)
    tracer = metrics = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()
    if args.metrics:
        from repro.obs import MetricsRegistry
        metrics = MetricsRegistry()
    resume = args.resume and os.path.exists(os.path.join(args.ckpt, "LATEST"))
    if resume:
        from repro.train import checkpoint as ckpt_lib
        print(f"[train] resumed at step {ckpt_lib.latest_step(args.ckpt)}")
    if args.supervise:
        from repro.train.supervisor import run_supervised
        res = run_supervised(cfg, latency=PaperCalibrated(), tracer=tracer,
                             metrics=metrics)
    else:
        res = run_experiment(cfg, latency=PaperCalibrated(), resume=resume,
                             save_final=True, tracer=tracer, metrics=metrics)
    for e in res.recovery_log:
        fields = " ".join(f"{k}={v}" for k, v in e.items() if k != "event")
        print(f"[train] recovery: {e['event']} {fields}")
    for m in res.metrics:
        print(f"[train] step {m['step']:5d} loss {m['loss']:.4f} "
              f"sim {m['sim_time']:8.1f}s selected {m['selected']} "
              f"staleness {m['staleness']:.1f}")
    print(f"[train] done: {res.steps} steps, sim_time {res.sim_time:.0f}s, "
          f"mean_selected {res.mean_selected:.2f}, "
          f"mean_staleness {res.mean_staleness:.2f}, "
          f"restarts {res.restarts}, checkpoint {args.ckpt}")
    if res.phase_times:
        breakdown = " ".join(f"{k} {v:.2f}s"
                             for k, v in sorted(res.phase_times.items()))
        print(f"[train] wall {res.wall_time_s:.2f}s ({breakdown})")
    if tracer is not None:
        tracer.export(args.trace)
        print(f"[train] trace: {args.trace} ({len(tracer)} events, "
              f"{tracer.dropped} dropped)")
    if metrics is not None:
        metrics.dump_jsonl(args.metrics)
        print(f"[train] metrics: {args.metrics} ({len(metrics)} series)")
    return res


if __name__ == "__main__":
    main()
