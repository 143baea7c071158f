"""Faults planted in the timed path, for the tests that show ``correct``
comes out false when the program is broken underneath the harness.

Each is a ``mutate(trainer)`` for ``harness.run_cell``: it replaces the
trainer's step after set-up has built it and before the first step, so
the set-up steps and the window both run the broken step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def state_unchanged(tr) -> None:
    """A step that computes, reports its loss, and returns its state
    unchanged."""
    step = tr.train_step

    def broken(p, o, e, s, b, m):
        copies = jax.tree_util.tree_map(jnp.copy, (p, o, e))
        return (p, o, e, step(*copies, s, b, m)[3])

    tr.train_step = broken


def half_batch(tr) -> None:
    """Half of the batch left out and the mean taken over the rest: half
    of each worker's rows, or with one row each, half of the workers."""
    from repro.train.train_step import build_train_step

    agg = tr.cfg.aggregation
    w, n = agg.total_workers, agg.num_workers
    rows = tr.cfg.shape.global_batch // w
    if rows > 1:
        step = tr.train_step

        def broken(p, o, e, s, b, m):
            half = {k: v.reshape((w, rows) + v.shape[1:])[:, :rows // 2]
                    .reshape((w * (rows // 2),) + v.shape[1:])
                    for k, v in b.items()}
            return step(p, o, e, s, half, m)
    else:
        fewer = jax.jit(build_train_step(
            tr.model, tr.optimizer, num_workers=w // 2, n_aggregate=n // 2,
            ema_decay=tr.cfg.optimizer.ema_decay), donate_argnums=(0, 1, 2))

        def broken(p, o, e, s, b, m):
            return fewer(p, o, e, s, {k: v[:w // 2] for k, v in b.items()},
                         m[:w // 2])

    tr.train_step = broken


def no_exchange(tr) -> None:
    """The SPMD engine with the exchange between chips left out: each
    shard's masked reduce, and no psum over 'data'. Patches the engine
    module for the rest of the process."""
    from repro.distributed import spmd_engine

    reduce = spmd_engine.reduce_then_psum

    def local_only(*args, axis_name=None, **kwargs):
        return reduce(*args, axis_name=None, **kwargs)

    spmd_engine.reduce_then_psum = local_only
