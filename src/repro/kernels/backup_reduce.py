"""Pallas TPU kernel: masked backup-worker gradient reduction.

The on-chip half of the paper's Alg. 4 line 7: given W stacked worker
gradients (one shard each, flattened) and the [W] selection mask, produce
(1/N) * sum_{selected} g_w as a single fused pass — a [W] x [W, BN] matvec
per grid block, with the gradient tile streamed through VMEM once (the op
is bandwidth-bound; fusing mask+scale+reduce avoids a second HBM pass over
the W-times-larger stacked buffer).

Grid: 1-D over flattened-parameter blocks. Mask lives in a [W, 1] VMEM
block replicated to every grid step.

Block rule (``choose_block``): the lane count per grid step is the
largest multiple of 1,024 whose [W, block] input tile fits
``TILE_BYTES``, capped at N (a block of N is the full dimension). A
fixed 4,096 lanes made the call step-bound: qwen3-0.6b's 596,049,920
lanes ran 145,520 grid steps of 48 KB at W = 2, each ~0.325 us (the
fixed cost of a Pallas TPU grid step, 47.3 ms a call on a v5e), where
the bytes alone take 8.7 ms at 819 GB/s. At 262,144 lanes the same call
is 2,274 steps. The last block may be ragged: Pallas clips its writes
at N, and every lane is reduced on its own, so what the last tile holds
past N reaches no output lane. The stack is never padded (a pad would
copy the whole [W, N] stack).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import interpret_mode

# VMEM budget of one [W, block] input tile. Compiled for a v5e under the
# default scoped VMEM at N = 596,049,920: a 2 MiB tile compiles (f32
# W = 2 at 262,144 lanes, bf16 W = 8 at 131,072), a 4 MiB tile is
# refused with RESOURCE_EXHAUSTED (f32 W = 2 at 524,288, bf16 W = 8 at
# 262,144).
TILE_BYTES = 2 << 20
# lane granule of a block smaller than N: the [N] f32 output's TPU layout
# is tiled by 1,024 lanes (T(1024)), and Mosaic refuses a 128-lane block
LANES = 1024


def choose_block(w: int, n: int, itemsize: int) -> int:
    """Lanes per grid step for a [w, n] stack of ``itemsize``-byte
    elements: the largest multiple of ``LANES`` whose input tile fits
    ``TILE_BYTES`` (at least one granule), or ``n`` when that is
    smaller."""
    fit = TILE_BYTES // (w * itemsize) // LANES * LANES
    return min(max(fit, LANES), n)


def _reduce_kernel(g_ref, m_ref, o_ref, *, inv_n: float):
    g = g_ref[...].astype(jnp.float32)              # [W, BN]
    m = m_ref[...]                                  # [W, 1] f32
    # broadcast-multiply-sum over the worker rows: Mosaic refuses a dot
    # that contracts a 1-D operand, and with W rows the MXU would sit
    # idle anyway — the pass is bound by streaming the [W, BN] tile
    o_ref[...] = (jnp.sum(g * m, axis=0) * inv_n).astype(o_ref.dtype)


def backup_reduce(grads: jnp.ndarray, mask: jnp.ndarray, n_aggregate: int, *,
                  block: Optional[int] = None,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """grads: [W, N] stacked worker grads; mask: [W] -> [N] masked mean.

    N may be any size: the grid is ``cdiv(N, block)`` and a ragged last
    block is clipped by Pallas, never padded. ``block=None`` takes
    ``choose_block(W, N, grads.dtype.itemsize)``; an explicit ``block``
    is capped at N. Each lane is reduced over the same rows in the same
    order whatever the block, so the output does not depend on it.
    ``interpret=None`` follows the backend (``kernels.mode``).
    """
    w, n = grads.shape
    if block is None:
        block = choose_block(w, n, grads.dtype.itemsize)
    block = min(block, n)
    kernel = functools.partial(_reduce_kernel, inv_n=1.0 / n_aggregate)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, block),),
        in_specs=[
            pl.BlockSpec((w, block), lambda i: (0, i)),
            pl.BlockSpec((w, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        interpret=interpret_mode(interpret),
        name="backup_reduce",           # the kernel's name on a device trace
    )(grads, mask.astype(jnp.float32).reshape(w, 1))
