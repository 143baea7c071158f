"""Pallas TPU kernel: masked backup-worker gradient reduction.

The on-chip half of the paper's Alg. 4 line 7: given W stacked worker
gradients (one shard each, flattened) and the [W] selection mask, produce
(1/N) * sum_{selected} g_w as a single fused pass — a [W] x [W, BN] matvec
per grid block, with the gradient tile streamed through VMEM once (the op
is bandwidth-bound; fusing mask+scale+reduce avoids a second HBM pass over
the W-times-larger stacked buffer).

Grid: 1-D over flattened-parameter blocks. Mask lives in a [W, 1] VMEM
block replicated to every grid step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.mode import interpret_mode


def _reduce_kernel(g_ref, m_ref, o_ref, *, inv_n: float):
    g = g_ref[...].astype(jnp.float32)              # [W, BN]
    m = m_ref[...]                                  # [W, 1] f32
    # broadcast-multiply-sum over the worker rows: Mosaic refuses a dot
    # that contracts a 1-D operand, and with W rows the MXU would sit
    # idle anyway — the pass is bound by streaming the [W, BN] tile
    o_ref[...] = (jnp.sum(g * m, axis=0) * inv_n).astype(o_ref.dtype)


def backup_reduce(grads: jnp.ndarray, mask: jnp.ndarray, n_aggregate: int, *,
                  block: int = 4096,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """grads: [W, N] stacked worker grads; mask: [W] -> [N] masked mean.

    N may be any size: the flattened gradient is zero-padded up to the
    block multiple for the grid and the padding is sliced off the output
    (zeros reduce to zeros, so the padded lanes are inert).
    ``interpret=None`` follows the backend (``kernels.mode``).
    """
    w, n = grads.shape
    block = min(block, n)
    pad = (-n) % block
    if pad:
        grads = jnp.pad(grads, ((0, 0), (0, pad)))
    padded = n + pad
    kernel = functools.partial(_reduce_kernel, inv_n=1.0 / n_aggregate)
    out = pl.pallas_call(
        kernel,
        grid=(padded // block,),
        in_specs=[
            pl.BlockSpec((w, block), lambda i: (0, i)),
            pl.BlockSpec((w, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((padded,), jnp.float32),
        interpret=interpret_mode(interpret),
    )(grads, mask.astype(jnp.float32).reshape(w, 1))
    return out[:n] if pad else out
