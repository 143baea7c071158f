"""Smoke run of the N+b backup-worker trainer on TPU, at qwen3-0.6b's
published widths (28 layers, d_model 1024, vocab 151936), random weights
from a seed.

    python chip_smoke.py             # one chip: trainer, resume, kernel
    python chip_smoke.py --chips 4   # four chips: SPMD engine vs sim only

Every phase drives the CLI entry point a user calls,
``repro.launch.train.main(argv)``, in this one process (a child could
not take the chip this process holds). With no TPU it exits non-zero
before running any phase. A failed phase makes the exit code non-zero;
nothing is caught and logged away. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
and is printed only when every phase passed.

The wall times printed are smoke timings of this run, not metrics.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import math
import os
import re
import shutil
import sys
import tempfile
import time
import warnings

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Per-step losses of two runs that should agree are compared with this
# relative tolerance: bf16 keeps 8 significant bits (a relative ulp of
# 2**-8), the fused lax.scan chunk and the per-step program (or the
# mesh engine's explicit per-worker gradient sum and the sim backend's
# mask-weighted loss) may round in a different order, and a few optimizer
# steps compound that by a few ulps.
LOSS_RTOL = 4 * 2.0 ** -8

ARCH = ["--arch", "qwen3-0.6b"]
STEPS = 8
RESUME_STEPS = 4
# The paper's N+b layout on one chip: 6 aggregated + 2 backup workers,
# 4 rows of 64 tokens each. The host straggler backend: the device one
# refuses vocabularies above 46340 (data/synthetic_lm.py). A learning
# rate for a from-scratch model of this width; the CLI default is sized
# for the smoke model.
TRAIN_ARGS = ["--strategy", "backup", "--workers", "6", "--backups", "2",
              "--batch-per-worker", "4", "--seq", "64",
              "--straggler-backend", "host", "--lr", "0.0001",
              "--log-every", "1", "--ckpt-every", "0", "--seed", "0"]


class _Tee(io.TextIOBase):
    """Copy of everything written, passed on to the real stdout."""

    def __init__(self, out):
        self.out, self.buf = out, io.StringIO()

    def write(self, s):
        self.buf.write(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()


def require_tpu():
    """The devices, or SystemExit when JAX finds no TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found "
                         f"{devices[0].platform!r} devices); nothing run")
    return devices


def train(argv):
    """``launch.train.main(argv)`` -> (TrainResult, its stdout, wall s).

    Warnings raised by the program's own modules (such as the Trainer's
    fallback from the spmd engine to the sim backend) are errors here."""
    from repro.launch import train as train_cli
    tee = _Tee(sys.stdout)
    t0 = time.perf_counter()
    with warnings.catch_warnings(), contextlib.redirect_stdout(tee):
        warnings.filterwarnings("error", module=r"repro(\.|$)")
        res = train_cli.main(argv)
    return res, tee.buf.getvalue(), time.perf_counter() - t0


def dispatch_times(trace_path):
    """Wall seconds of each traced dispatch (the first one compiles)."""
    from repro.obs.trace import load_trace
    events = load_trace(trace_path)["traceEvents"]
    return [e["dur"] / 1e6 for e in events
            if e.get("ph") == "X" and e["name"] in ("train/step",
                                                      "train/chunk")]


def _series(metrics):
    return tuple([m[k] for m in metrics]
                 for k in ("step", "loss", "selected", "sim_time"))


def compare_runs(a, b, what):
    """Two runs' per-step metrics: same steps, masks and simulated clock;
    losses finite and within LOSS_RTOL. Returns the largest relative
    loss difference."""
    steps_a, loss_a, sel_a, sim_a = _series(a)
    steps_b, loss_b, sel_b, sim_b = _series(b)
    if steps_a != steps_b:
        raise AssertionError(f"{what}: logged steps differ: "
                             f"{steps_a} vs {steps_b}")
    bad = [l for l in loss_a + loss_b if not math.isfinite(l)]
    if bad:
        raise AssertionError(f"{what}: non-finite losses {bad}")
    if sel_a != sel_b:
        raise AssertionError(f"{what}: selected differs: {sel_a} vs {sel_b}")
    if sim_a != sim_b:
        raise AssertionError(f"{what}: sim_time differs: {sim_a} vs {sim_b}")
    worst = max(abs(x - y) / abs(y) for x, y in zip(loss_a, loss_b))
    if worst > LOSS_RTOL:
        raise AssertionError(f"{what}: losses differ by {worst:.3g} "
                             f"relative (> {LOSS_RTOL:.3g}): "
                             f"{loss_a} vs {loss_b}")
    return worst


def peak_bytes(device):
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        raise AssertionError(f"{device} reports no peak_bytes_in_use")
    return stats["peak_bytes_in_use"]


def model_widths(model_args):
    """(config, param count) of the model the runs build."""
    import jax
    from repro import configs
    from repro.models import get_model
    cfg = (configs.get_smoke_config(model_args[1])
           if "--smoke" in model_args else configs.get_config(model_args[1]))
    shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    n = sum(math.prod(s.shape) for s in jax.tree_util.tree_leaves(shapes))
    return cfg, n


def phase_trainer(workdir, model_args=ARCH, steps=STEPS):
    """Chunk 1 and chunk 4 on one seed: finite losses, identical masks
    and simulated clock, losses within LOSS_RTOL. Leaves the chunk-4
    run's final checkpoint in ``<workdir>/ckpt`` for the resume phase."""
    runs = {}
    for chunk in (1, 4):
        ckpt = os.path.join(workdir, f"ckpt_c{chunk}")
        trace = os.path.join(workdir, f"trace_c{chunk}.json")
        res, _, wall = train(model_args + TRAIN_ARGS + [
            "--steps", str(steps), "--chunk-size", str(chunk),
            "--ckpt", ckpt, "--trace", trace])
        times = dispatch_times(trace)
        print(f"[smoke] chunk {chunk}: smoke timing, not a metric: "
              f"main {wall:.2f}s, first dispatch (compile) "
              f"{times[0]:.2f}s, later dispatches "
              f"{[round(t, 4) for t in times[1:]]}s")
        runs[chunk] = res.metrics
        del res                       # frees the run's device state
        gc.collect()
        if chunk == 1:
            shutil.rmtree(ckpt)       # one full-width checkpoint on disk
    worst = compare_runs(runs[1], runs[4], "chunk 1 vs chunk 4")
    exact = _series(runs[1])[1] == _series(runs[4])[1]
    print(f"[smoke] chunk 1 vs chunk 4: selected and sim_time identical; "
          f"losses {'bit-identical' if exact else 'not bit-identical'} "
          f"(max relative difference {worst:.3g}, tolerance "
          f"{LOSS_RTOL:.3g})")
    os.rename(os.path.join(workdir, "ckpt_c4"), os.path.join(workdir, "ckpt"))


def phase_resume(workdir, model_args=ARCH, steps=STEPS, more=RESUME_STEPS):
    """``--resume`` from the checkpoint the trainer phase left: prints
    ``resumed at step <steps>`` and continues to ``steps + more``."""
    res, out, wall = train(model_args + TRAIN_ARGS + [
        "--steps", str(more), "--chunk-size", "4", "--resume",
        "--ckpt", os.path.join(workdir, "ckpt")])
    m = re.search(r"resumed at step (\d+)", out)
    if m is None or int(m.group(1)) != steps:
        raise AssertionError(f"expected 'resumed at step {steps}' in the "
                             f"output, got {m and m.group(0)!r}")
    last = [x["step"] for x in res.metrics]
    losses = [x["loss"] for x in res.metrics]
    if last != list(range(steps + 1, steps + more + 1)):
        raise AssertionError(f"resume logged steps {last}")
    if not all(math.isfinite(l) for l in losses):
        raise AssertionError(f"resume: non-finite losses {losses}")
    print(f"[smoke] resume: resumed at step {steps}, continued to step "
          f"{last[-1]}; smoke timing, not a metric: main {wall:.2f}s")
    del res
    gc.collect()


def phase_kernel(num_params, workers=2, interpret=False):
    """``kernels.backup_reduce`` natively (``interpret=False``) on the
    model's flattened width against the jnp masked mean on the same
    device, the reference at full f32 matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.backup_reduce import backup_reduce
    from repro.kernels.bucketed_reduce import ref_masked_mean

    grads = jax.random.normal(jax.random.PRNGKey(1), (workers, num_params),
                              jnp.float32)
    n_agg = workers - 1
    kernel = jax.jit(lambda g, m: backup_reduce(g, m, n_agg,
                                                interpret=interpret))
    with jax.default_matmul_precision("highest"):
        ref_fn = jax.jit(lambda g, m: ref_masked_mean(g, m, n_agg))
    # every worker selected, then the last one left out as a straggler
    for mask in (np.ones(workers, np.float32),
                 np.r_[np.ones(workers - 1), 0.0].astype(np.float32)):
        t0 = time.perf_counter()
        out = jax.block_until_ready(kernel(grads, mask))
        dt = time.perf_counter() - t0
        ref = ref_fn(grads, mask)
        err = float(jnp.max(jnp.abs(out - ref)))
        scale = float(jnp.max(jnp.abs(ref)))
        if out.shape != (num_params,) or not err <= 1e-6 * scale:
            raise AssertionError(
                f"backup_reduce vs jnp, mask {mask}: shape {out.shape}, "
                f"max abs error {err:.3g} at scale {scale:.3g}")
        print(f"[smoke] kernel: backup_reduce W={workers} P={num_params} "
              f"mask {mask.tolist()} interpret={interpret} matches the jnp "
              f"masked mean (max abs error {err:.3g}, max |ref| "
              f"{scale:.3g}); smoke timing, not a metric: call {dt:.3f}s")
        del out, ref
    del grads
    gc.collect()


# The four-chip layout is the one-chip job's 6+2 workers, two per chip
# (W_local = 2, so the native backup_reduce runs inside the step). Each
# chip holds the replicated params, optimizer state and EMA (8.34 GB)
# and two f32 flattened gradients (2.38 GB each): compiled for a
# described v5e:2x2 the step needs 15.50 GB per chip of the 16.91 GB
# (15.75 GiB) the compiler allows.


def phase_spmd(workdir, devices, model_args=ARCH, steps=4):
    """``--execution spmd --mesh-data <devices>`` against the sim backend
    on the same seed and masks: identical ``selected``, losses within
    LOSS_RTOL. Returns each device's peak bytes after the spmd run."""
    n = len(devices)
    common = model_args + TRAIN_ARGS + ["--steps", str(steps),
                                        "--chunk-size", "1"]
    spmd, _, wall_spmd = train(common + [
        "--execution", "spmd", "--mesh-data", str(n),
        "--ckpt", os.path.join(workdir, "ckpt_spmd")])
    peaks = [peak_bytes(d) for d in devices]
    spmd_metrics = spmd.metrics
    del spmd
    gc.collect()
    shutil.rmtree(os.path.join(workdir, "ckpt_spmd"))
    sim, _, wall_sim = train(common + [
        "--ckpt", os.path.join(workdir, "ckpt_sim")])
    sim_metrics = sim.metrics
    del sim
    gc.collect()
    shutil.rmtree(os.path.join(workdir, "ckpt_sim"))
    worst = compare_runs(spmd_metrics, sim_metrics,
                         f"spmd ({n} devices) vs sim")
    print(f"[smoke] spmd mesh-data {n} vs sim: selected and sim_time "
          f"identical, losses within {worst:.3g} relative (tolerance "
          f"{LOSS_RTOL:.3g}); smoke timing, not a metric: spmd main "
          f"{wall_spmd:.2f}s, sim main {wall_sim:.2f}s")
    return peaks


def _print_widths(model_args):
    cfg, n = model_widths(model_args)
    print(f"[smoke] model {cfg.name}: {cfg.num_layers}L d_model "
          f"{cfg.d_model} heads {cfg.num_heads}/{cfg.num_kv_heads} hd "
          f"{cfg.resolved_head_dim} d_ff {cfg.d_ff} vocab {cfg.vocab_size}, "
          f"{n:,} params")
    return n


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the SPMD engine on a 4-chip 'data' "
                         "mesh against the sim backend")
    args = ap.parse_args(argv)
    devices = require_tpu()
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} TPU devices, found {len(devices)}")
    from repro.launch.mesh import init_compile_cache
    print(f"[smoke] compile cache: {init_compile_cache()}")
    print(f"[smoke] devices: {len(devices)} x {devices[0].device_kind}")
    n_params = _print_widths(ARCH)
    workdir = tempfile.mkdtemp(prefix=".chip_smoke_", dir=ROOT)
    try:
        if args.chips == 4:
            peaks = phase_spmd(workdir, devices[:4])
            for d, p in zip(devices, peaks):
                print(f"[smoke] {d}: peak_bytes_in_use {p:,}")
            # the replicated state alone is the same on every device
            low = [d for d, p in zip(devices[:4], peaks) if p < peaks[0] / 2]
            if low:
                raise AssertionError(f"little or no work on {low}: the "
                                     f"step ran on device 0")
        else:
            phase_trainer(workdir)
            print(f"[smoke] peak_bytes_in_use after trainer: "
                  f"{peak_bytes(devices[0]):,}")
            phase_resume(workdir)
            shutil.rmtree(os.path.join(workdir, "ckpt"))
            phase_kernel(n_params)
            print(f"[smoke] peak_bytes_in_use: {peak_bytes(devices[0]):,}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
