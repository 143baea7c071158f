"""SPMD train-step builder: model + optimizer + the paper's aggregation.

The step signature is

    (params, opt_state, ema, step, batch, mask) ->
        (params, opt_state, ema, metrics)

where ``mask`` is the [W] backup-worker selection for THIS step (host-
computed by the StragglerSimulator; all-ones for plain Sync-Opt). The
masked aggregation is realized by weighting per-example losses (see
repro.core.sync_backup) so the normal data-parallel gradient psum performs
Alg. 4's "mean of the fastest N" exactly.

Sync-Opt needs no gradient clipping (paper §A.3) — clipping is only
applied when the config asks for it (the async simulator does).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ema as ema_lib
from repro.core import straggler_jax
from repro.core import sync_backup
from repro.optim import optimizers as opt_lib


def make_loss_fn(model, num_workers: int, n_aggregate: int) -> Callable:
    """Builds loss(params, batch, mask) -> (scalar, metrics)."""

    def loss_fn(params, batch, mask):
        per_tok, aux = model.per_token_loss(params, batch)
        labels = batch["labels"]
        if per_tok.shape[1] != labels.shape[1]:       # vlm prefix positions
            pad = per_tok.shape[1] - labels.shape[1]
            labels = jnp.concatenate(
                [jnp.full((labels.shape[0], pad), -1, labels.dtype), labels], 1)
        valid = (labels >= 0).astype(jnp.float32)
        per_ex = (jnp.sum(per_tok * valid, axis=-1)
                  / jnp.maximum(jnp.sum(valid, axis=-1), 1.0))
        main = sync_backup.weighted_loss(per_ex, mask, n_aggregate)
        # monitoring loss: plain mean over the *selected* workers — divide
        # by the realized selection fraction so Timeout's variable counts
        # don't skew the reading
        sel = jnp.sum(per_ex * sync_backup.per_example_weights(
            mask, per_ex.shape[0], n_aggregate))
        frac = jnp.sum(mask.astype(jnp.float32)) / n_aggregate
        total = main + aux
        metrics = {"loss": sel / jnp.maximum(frac, 1e-6), "aux_loss": aux}
        return total, metrics

    return loss_fn


def _microbatch_split(batch: Dict[str, jnp.ndarray], num_workers: int,
                      num_microbatches: int) -> Dict[str, jnp.ndarray]:
    """[B, ...] -> [M, B/M, ...] such that every microbatch contains an
    equal slice of EVERY worker's shard (workers own contiguous row blocks,
    so the mask-weighted aggregation stays exact per microbatch)."""
    def split(x):
        b = x.shape[0]
        per = b // num_workers
        per_mb = per // num_microbatches
        x = x.reshape((num_workers, num_microbatches, per_mb) + x.shape[1:])
        x = jnp.swapaxes(x, 0, 1)
        return x.reshape((num_microbatches, num_workers * per_mb) + x.shape[3:])

    return jax.tree_util.tree_map(split, batch)


def build_train_step(model, optimizer: opt_lib.Optimizer, *, num_workers: int,
                     n_aggregate: int, ema_decay: float = 0.0,
                     clip_norm: float = 0.0, num_microbatches: int = 1,
                     grad_shardings: Any = None) -> Callable:
    """num_microbatches > 1 enables gradient accumulation: the batch is
    scanned in M slices and per-microbatch gradients are accumulated in an
    f32 tree. When ``grad_shardings`` is given, the accumulator is
    constrained to it (data-axes sharded => the DP all-reduce becomes a
    reduce-scatter and the accumulator stays ZeRO-2-sharded)."""
    loss_fn = make_loss_fn(model, num_workers, n_aggregate)

    def compute_grads(params, batch, mask):
        if num_microbatches <= 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch, mask)
            if grad_shardings is not None:
                grads = jax.lax.with_sharding_constraint(grads, grad_shardings)
            return grads, metrics

        mb = _microbatch_split(batch, num_workers, num_microbatches)

        def body(acc, mb_batch):
            (loss, metrics), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb_batch, mask)
            if grad_shardings is not None:
                g = jax.lax.with_sharding_constraint(g, grad_shardings)
            acc = jax.tree_util.tree_map(
                lambda a, x: a + x.astype(jnp.float32), acc, g)
            return acc, metrics

        zeros = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if grad_shardings is not None:
            zeros = jax.lax.with_sharding_constraint(zeros, grad_shardings)
        acc, metrics_stack = jax.lax.scan(body, zeros, mb)
        grads = jax.tree_util.tree_map(lambda a: a / num_microbatches, acc)
        metrics = jax.tree_util.tree_map(jnp.mean, metrics_stack)
        return grads, metrics

    def train_step(params, opt_state, ema_state, step, batch, mask):
        # named scopes are HLO metadata only (the profiler's op_name):
        # they name the step's phases on a device trace and change no op
        with jax.named_scope("grad"):
            grads, metrics = compute_grads(params, batch, mask)
        with jax.named_scope("optimizer"):
            if clip_norm > 0:
                grads, gnorm = opt_lib.clip_by_global_norm(grads, clip_norm)
                metrics["grad_norm"] = gnorm
            new_params, new_opt, stats = optimizer.apply(params, grads,
                                                         opt_state, step)
        metrics.update(stats)
        if ema_decay > 0:
            with jax.named_scope("ema"):
                ema_state = ema_lib.update(ema_state, new_params, ema_decay)
        return new_params, new_opt, ema_state, metrics

    return train_step


def build_chunk_step(model, optimizer: opt_lib.Optimizer, *, num_workers: int,
                     n_aggregate: int, ema_decay: float = 0.0,
                     clip_norm: float = 0.0, num_microbatches: int = 1,
                     grad_shardings: Any = None, sample_fn: Callable = None,
                     select_fn: Callable = None,
                     data_fn: Callable = None) -> Callable:
    """Fused K-step trainer: one ``lax.scan`` dispatch per chunk.

    Host-mask mode (``sample_fn is None``) — masks precomputed by the host
    StragglerSimulator, stacked and shipped with the batch:

        chunk(params, opt, ema, step0, batches [K,B,...], masks [K,W])
            -> (params, opt, ema, metrics {k: [K]})

    Device mode (``sample_fn``/``select_fn``/``data_fn`` given) — batch
    generation, arrival sampling AND mask selection all run inside the
    scan body; sim_time accumulates in the carry and everything syncs to
    host once per chunk (``k`` is static — one compile per chunk length):

        chunk(params, opt, ema, step0, k, dead [W], key)
            -> (params, opt, ema, metrics {k: [K]}, masks [K,W], times [K])

    Both modes advance ``step`` in the carry so lr schedules see the same
    per-step values as the legacy path; the scan body is the unmodified
    ``build_train_step`` function, which XLA compiles to the same
    per-iteration arithmetic — the chunked host path is bit-identical to K
    sequential dispatches (tests/test_chunked_loop.py).
    """
    step_fn = build_train_step(
        model, optimizer, num_workers=num_workers, n_aggregate=n_aggregate,
        ema_decay=ema_decay, clip_norm=clip_norm,
        num_microbatches=num_microbatches, grad_shardings=grad_shardings)

    def scan_steps(params, opt_state, ema_state, step0, batches, masks):
        """The one scan both modes share: K steps over stacked (batch, mask)."""
        def body(carry, xs):
            p, o, e, step = carry
            batch, mask = xs
            p, o, e, m = step_fn(p, o, e, step, batch, mask)
            return (p, o, e, step + 1), m

        (p, o, e, _), ms = jax.lax.scan(
            body, (params, opt_state, ema_state, step0), (batches, masks))
        return p, o, e, ms

    if sample_fn is None:
        return scan_steps

    if select_fn is None or data_fn is None:
        raise ValueError("device mode needs sample_fn, select_fn and data_fn")

    def chunk(params, opt_state, ema_state, step0, k, dead, key):
        # All chunk randomness is generated vectorized up front
        # (straggler_jax.chunk_arrivals — per-step fold_in streams, so
        # results are invariant to chunk partitioning) instead of inside
        # the scan body: hoisting the threefry expansion keeps the scan
        # body at the bare train-step cost.
        steps = step0 + jnp.arange(k, dtype=step0.dtype)
        batches = jax.vmap(data_fn)(steps)
        arrivals = straggler_jax.chunk_arrivals(sample_fn, key, steps,
                                                dead.shape[0], dead)
        masks, times = jax.vmap(select_fn)(arrivals)
        masks = masks & ~dead[None, :]
        p, o, e, ms = scan_steps(params, opt_state, ema_state, step0,
                                 batches, masks)
        return p, o, e, ms, masks, times

    return chunk


def build_event_chunk_step(grad_fn: Callable, update_fn: Callable, strategy,
                           *, ema_decay: float = 0.0) -> Callable:
    """Fused K-arrival event engine: one ``lax.scan`` dispatch per chunk.

        chunk(params, opt_state, ema, workers [W, ...], aux,
              batches [K, b, ...], rows {name: [K]})
            -> (params, opt_state, ema, workers, aux, losses [K])

    ``workers`` is the stacked per-worker read-parameter pytree (one
    ``[W, ...]`` device tree instead of W host copies); ``aux`` is the
    strategy's device carry (``init_scan_state`` — softsync gradient
    window / staleness ring buffer); ``rows`` is the host-precomputed
    :class:`repro.core.coordination.EventPlan` (``plan.rows()``). Per
    arrival the body gathers the worker's read copy, runs grad_fn, lets
    the strategy aggregate-or-buffer (``on_arrival_scan``), conditionally
    applies the optimizer + EMA (``row["apply"]`` — host-planned, since
    every built-in strategy's verdict is gradient-independent), and
    scatters the fresh params back to the worker's row. The scan replays
    ``run_events``' exact update/staleness sequence because all control
    flow comes from the plan (tests/test_event_scan.py).
    """

    def chunk(params, opt_state, ema_state, workers, aux, batches, rows):
        def body(carry, xs):
            p, o, e, w_stack, ax = carry
            batch, row = xs
            read = jax.tree_util.tree_map(lambda s: s[row["worker"]], w_stack)
            loss, grads = grad_fn(read, batch)
            ax, agg = strategy.on_arrival_scan(ax, grads, row)

            def apply_update(p, o, e):
                out = update_fn(p, o, agg, row["step"])
                p2, o2 = out[0], out[1]
                if ema_decay > 0:
                    e = ema_lib.update(e, p2, ema_decay)
                return p2, o2, e

            p, o, e = jax.lax.cond(row["apply"], apply_update,
                                   lambda p, o, e: (p, o, e), p, o, e)
            # the worker reads the fresh params for its next mini-batch
            w_stack = jax.tree_util.tree_map(
                lambda s, x: s.at[row["worker"]].set(x), w_stack, p)
            return (p, o, e, w_stack, ax), loss

        (p, o, e, w_stack, ax), losses = jax.lax.scan(
            body, (params, opt_state, ema_state, workers, aux),
            (batches, rows))
        return p, o, e, w_stack, ax, losses

    return chunk


def build_eval_step(model) -> Callable:
    def eval_step(params, batch):
        per_tok, _ = model.per_token_loss(params, batch)
        labels = batch["labels"]
        if per_tok.shape[1] != labels.shape[1]:
            pad = per_tok.shape[1] - labels.shape[1]
            labels = jnp.concatenate(
                [jnp.full((labels.shape[0], pad), -1, labels.dtype), labels], 1)
        valid = (labels >= 0).astype(jnp.float32)
        return jnp.sum(per_tok * valid) / jnp.maximum(jnp.sum(valid), 1.0)

    return eval_step


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs for lowering — shannon/kernels pattern)
# ---------------------------------------------------------------------------


def input_specs(cfg, shape, *, num_workers: int) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every train-step input."""
    b, s = shape.global_batch, shape.seq_len
    batch: Dict[str, Any] = {}
    if cfg.family == "vlm":
        p = cfg.num_prefix_embeds
        batch["tokens"] = jax.ShapeDtypeStruct((b, s - p), jnp.int32)
        batch["labels"] = jax.ShapeDtypeStruct((b, s - p), jnp.int32)
        batch["prefix_embeds"] = jax.ShapeDtypeStruct((b, p, cfg.d_model),
                                                      jnp.bfloat16)
    elif cfg.family == "audio":
        batch["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        batch["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        batch["encoder_frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_seq_len, cfg.d_model), jnp.bfloat16)
    else:
        batch["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        batch["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    return {
        "batch": batch,
        "mask": jax.ShapeDtypeStruct((num_workers,), jnp.bool_),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
