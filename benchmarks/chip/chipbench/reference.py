"""The plain reference: the N+b training step in float32 jax.numpy.

It imports nothing of the program. It takes the same weights (made again
from the seed by ``model_spec.init_fn``), the same batches (``feed``)
and the straggler arrival times the run recorded, selects the fastest N
workers itself, and runs three steps of

    loss_w  = mean over worker w's rows of the mean next-token loss,
              plus the layers' auxiliary terms
    g       = (1/N) * sum over the selected w of grad loss_w
    ms      = decay * ms + (1 - decay) * g^2
    mom     = momentum * mom + lr * g / sqrt(ms + eps)
    theta   = round_to_param_dtype(theta - mom)
    ema     = ema_decay * ema + (1 - ema_decay) * theta

with ``lr`` the configured rate times N (the paper's rule).

This file is generic; the model is the architecture's
(``archs/<model_type>.py``), in three parts over float32 parameters:

    embed(p, tokens, s) -> x            p: the parameters outside the
                                        segments
    segments(s) -> [(seg_key, layer)]   in order; ``params[seg_key]``
                                        stacks the segment's layers on a
                                        leading axis, and
                                        ``layer(p_l, x, s, cast)`` gives
                                        x, or (x, aux) with aux a scalar
                                        added to the loss
    head_loss(p, x, labels, s, cast)    the mean next-token loss

``cast`` goes around both operands of every matrix product (see
below); the architecture runs each at ``Precision.HIGHEST``, so it is
float32 on the TPU too.

Parameters are stored in the configured dtype between steps, as the
configuration states; everything else is float32. Memory: gradients
are taken worker by worker and, inside a worker, layer by layer (the
forward keeps each layer's input; the backward recomputes one layer at
a time, segment after segment in reverse, and adds its gradient into
the accumulator in place), so a step holds the weights, the optimizer
state, the EMA, one float32 gradient and one worker's activations.

``precision="low"`` is the control: the same arithmetic with both
operands of every matrix product, and the cotangents flowing back into
them, rounded to the precision below the configured one (scaled fp8
e4m3 under bfloat16 parameters, bfloat16 under float32 ones).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import model_spec, spec


# ---------------------------------------------------------------------------
# Precision of the matrix products
# ---------------------------------------------------------------------------


def _round_fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _rounding(fn):
    @jax.custom_vjp
    def r(x):
        return fn(x)

    r.defvjp(lambda x: (fn(x), None), lambda _, g: (fn(g),))
    return r


LOWER = {"bfloat16": _rounding(_round_fp8), "float32": _rounding(_round_bf16)}


def operand_cast(dtype: str, precision: str):
    if precision == "f32":
        return lambda x: x
    if precision == "low":
        return LOWER[dtype]
    raise ValueError(f"precision {precision!r} (f32 | low)")


# ---------------------------------------------------------------------------
# One worker's gradient, layer by layer
# ---------------------------------------------------------------------------


def _f32(tree):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), tree)


def _outside(tree):
    """The parameters outside the layer-stacked segments."""
    return {k: v for k, v in tree.items() if not k.startswith("seg_")}


def _with_aux(layer, s, cast):
    """``layer`` as (p, x) -> (x, aux), aux 0 where it gives none."""
    def f(p, x):
        out = layer(p, x, s, cast)
        return out if isinstance(out, tuple) else (
            out, jnp.zeros((), jnp.float32))
    return f


def _worker_grad(acc, params, tokens, labels, weight, *, s, cast):
    """acc + weight * grad of one worker's loss; and that loss.

    Layer by layer: the forward keeps each layer's input, the backward
    recomputes one layer and adds its gradient into ``acc`` at that
    layer's index (acc is donated, so the update is in place). The
    embedding's gradient from the rows it gave is added to the head's
    gradient, then to ``acc``."""
    arch = spec.arch(s.model_type)
    segments = [(key, _with_aux(layer, s, cast))
                for key, layer in arch.segments(s)]
    outer = _f32(_outside(params))
    weight = jnp.asarray(weight, jnp.float32)
    x, embed_vjp = jax.vjp(lambda p: arch.embed(p, tokens, s), outer)
    aux = jnp.zeros((), jnp.float32)
    inputs = []
    for key, layer in segments:
        def fwd(carry, p_l, layer=layer):
            x, aux = carry
            y, a = layer(_f32(p_l), x)
            return (y, aux + a), x

        (x, aux), xs = jax.lax.scan(fwd, (x, aux), params[key])
        inputs.append(xs)
    loss, head_vjp = jax.vjp(
        lambda x, p: arch.head_loss(p, x, labels, s, cast), x, outer)
    ct, g_outer = head_vjp(weight)

    acc = dict(acc)
    for (key, layer), xs in reversed(list(zip(segments, inputs))):
        def bwd(carry, inputs, layer=layer):
            ct, acc_seg, i = carry
            p_l, x_in = inputs
            _, vjp = jax.vjp(layer, _f32(p_l), x_in)
            g_l, ct_in = vjp((ct, weight))
            acc_seg = jax.tree_util.tree_map(
                lambda a, g: jax.lax.dynamic_update_index_in_dim(
                    a, jax.lax.dynamic_index_in_dim(a, i, 0, False) + g,
                    i, 0),
                acc_seg, g_l)
            return (ct_in, acc_seg, i - 1), None

        count = jax.tree_util.tree_leaves(params[key])[0].shape[0]
        (ct, acc[key], _), _ = jax.lax.scan(
            bwd, (ct, acc[key], count - 1), (params[key], xs), reverse=True)
    g_outer = jax.tree_util.tree_map(jnp.add, g_outer, embed_vjp(ct)[0])
    acc.update(jax.tree_util.tree_map(jnp.add, _outside(acc), g_outer))
    return acc, loss + aux


def _update(params, ms, mom, ema, g, *, lr, decay, momentum, eps, ema_decay,
            dtype):
    ms = jax.tree_util.tree_map(lambda m, g_: decay * m + (1 - decay) * g_ * g_,
                                ms, g)
    mom = jax.tree_util.tree_map(
        lambda m, g_, v: momentum * m + lr * g_ / jnp.sqrt(v + eps), mom, g, ms)
    params = jax.tree_util.tree_map(
        lambda p, m: (p.astype(jnp.float32) - m).astype(dtype), params, mom)
    ema = jax.tree_util.tree_map(
        lambda e, p: ema_decay * e + (1 - ema_decay) * p.astype(jnp.float32),
        ema, params)
    return params, ms, mom, ema


# ---------------------------------------------------------------------------
# Readings: per-tensor norms, with layer-stacked leaves split by layer
# ---------------------------------------------------------------------------


SKETCH_KEY = 0x5EED
SKETCH_DIM = 8


def _stacked(name: str) -> bool:
    return name.startswith("['seg_")


def tensor_norms(tree):
    """{path: [L] or [1]}: the norm of each tensor, one per layer of a
    layer-stacked leaf."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(jnp.float32)
        name = jax.tree_util.keystr(path)
        axes = tuple(range(1, x.ndim)) if _stacked(name) else None
        out[name] = jnp.sqrt(jnp.sum(x * x, axis=axes)).reshape(-1)
    return out


def tensor_sketches(tree):
    """{path: [L or 1, SKETCH_DIM]}: each tensor's dot products with
    fixed random +-1 vectors. For two versions x and x + e of a tensor
    the sketches differ by about |e| in each coordinate, so comparing
    sketches estimates the norm of a difference without holding both
    sides at once."""
    out = {}
    key = jax.random.PRNGKey(SKETCH_KEY)
    for i, (path, x) in enumerate(jax.tree_util.tree_flatten_with_path(
            tree)[0]):
        x = x.astype(jnp.float32)
        name = jax.tree_util.keystr(path)
        axes = tuple(range(1 if _stacked(name) else 0, x.ndim))
        leaf_key = jax.random.fold_in(key, i)

        def dot(k, x=x, axes=axes, leaf_key=leaf_key):
            r = jax.random.rademacher(jax.random.fold_in(leaf_key, k),
                                      x.shape, jnp.float32)
            return jnp.sum(r * x, axis=axes)

        cols = jax.lax.map(dot, jnp.arange(SKETCH_DIM))      # [K] or [K, L]
        out[name] = cols.T.reshape(-1, SKETCH_DIM)
    return out


def per_tensor(values) -> Dict[str, Any]:
    """{path: [L or 1, ...]} on the device -> {path or path[layer]: row}
    on the host (a float for norms, an array for sketches)."""
    out = {}
    for name, v in values.items():
        v = np.asarray(v, np.float64)
        rows = {name: v[0]} if not _stacked(name) else {
            f"{name}[{i}]": row for i, row in enumerate(v)}
        out.update({k: float(r) if r.ndim == 0 else r
                    for k, r in rows.items()})
    return out


@jax.jit
def _change_norms(a, b):
    d = jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)
    return tensor_norms(d), tensor_sketches(d)


@jax.jit
def _norms_and_sketches(tree):
    return tensor_norms(tree), tensor_sketches(tree)


def norms(tree) -> Tuple[Dict[str, float], Dict[str, np.ndarray]]:
    """Per-tensor norms and sketches of ``tree``."""
    n, sk = _norms_and_sketches(tree)
    return per_tensor(n), per_tensor(sk)


def change_norms(a, b) -> Tuple[Dict[str, float], Dict[str, np.ndarray]]:
    """Per-tensor norms and sketches of ``a - b``."""
    n, sk = _change_norms(a, b)
    return per_tensor(n), per_tensor(sk)


# ---------------------------------------------------------------------------
# Three steps
# ---------------------------------------------------------------------------


def select(arrivals: np.ndarray, n: int) -> np.ndarray:
    """The fastest ``n`` workers of one step's arrival times."""
    mask = np.zeros(arrivals.shape[0], bool)
    mask[np.argsort(arrivals, kind="stable")[:n]] = True
    return mask


FAULTS = ("half", "no_exchange")


@jax.jit
def zeros(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), tree)


to_f32 = jax.jit(_f32)


@functools.lru_cache(maxsize=None)
def _programs(s, precision: str, lr: float, decay: float,
              momentum: float, eps: float, ema_decay: float):
    """The reference's jitted programs, built once per process for each
    model and optimizer setting (they hold no arrays)."""
    init = jax.jit(model_spec.init_fn(s))
    grad_fn = jax.jit(functools.partial(
        _worker_grad, s=s, cast=operand_cast(s.dtype, precision)),
        donate_argnums=(0,))
    update = jax.jit(functools.partial(
        _update, lr=lr, decay=decay, momentum=momentum, eps=eps,
        ema_decay=ema_decay, dtype=model_spec.DTYPES[s.dtype]),
        donate_argnums=(0, 1, 2, 3))
    return init, grad_fn, update


def run(config: Dict[str, Any], traffic, key, batches: Sequence[Dict],
        arrivals: Sequence[np.ndarray], *, precision: str = "f32",
        fault: Optional[str] = None, w_local: int = 0) -> Dict[str, Any]:
    """Three (or ``len(batches)``) reference steps from the seed's weights.

    ``fault`` puts a broken step in the program's place, to read what
    the comparison makes of it: ``"half"`` leaves half of the batch out
    (half of each worker's rows, or with one row each, half of the
    workers) and takes the mean over the rest; ``"no_exchange"`` is what
    the first chip of a mesh computes with the exchange between chips
    left out: only its own ``w_local`` workers, summed over N, in the
    gradient and in the loss it reports. Returns the readings
    the harness compares: per-step ``losses``, per-tensor ``grad`` norms
    of the first step's aggregated gradient, and per-tensor norms of the
    ``param_change`` and ``ema_change`` after the last step. Runs on the
    default device.
    """
    s = model_spec.sizes(config)
    opt = config["run"]["optimizer"]
    n = traffic.workers
    per = traffic.rows_per_worker
    lr = float(opt["lr"]) * (n if opt["scale_lr_with_workers"] else 1)
    init, grad_fn, update = _programs(
        s, precision, lr, float(opt["decay"]), float(opt["momentum"]),
        float(opt["eps"]), float(opt["ema_decay"]))

    params = init(key)
    ms, mom, ema = zeros(params), zeros(params), to_f32(params)
    losses: List[float] = []
    grad = None
    for step, (batch, arr) in enumerate(zip(batches, arrivals)):
        workers = list(np.nonzero(select(np.asarray(arr), n))[0])
        keep = range(per)
        if fault == "half" and per > 1:
            keep = range(per // 2)
        elif fault == "half":
            workers = [w for w in workers if w < traffic.total_workers // 2]
        elif fault == "no_exchange":
            workers = [w for w in workers if w < w_local]
        elif fault is not None:
            raise ValueError(f"fault {fault!r} (one of {FAULTS})")
        # the mean over the workers that count; without the exchange the
        # first chip still divides its partial sum by N
        weight = 1.0 / (n if fault in (None, "no_exchange") else len(workers))
        acc = zeros(params)
        worker_losses = []
        for w in workers:
            idx = np.asarray([w * per + r for r in keep])
            acc, loss = grad_fn(acc, params, jnp.asarray(batch["tokens"][idx]),
                                jnp.asarray(batch["labels"][idx]), weight)
            worker_losses.append(float(loss))
        losses.append(float(np.sum(worker_losses)) * weight)
        if step == 0:
            grad = norms(abs_tree(acc))
        params, ms, mom, ema = update(params, ms, mom, ema, acc)
    del ms, mom
    theta0 = init(key)
    return readings(losses, grad, change_norms(params, theta0),
                    change_norms(ema, theta0))


@jax.jit
def abs_tree(tree):
    return jax.tree_util.tree_map(jnp.abs, tree)


def readings(losses, grad, param_change, ema_change) -> Dict[str, Any]:
    """The readings both sides give: per-step losses; per-tensor norms
    (and sketches) of |first gradient|, of the weights' change and of
    the EMA's change."""
    return {"losses": losses, "grad": grad[0], "grad_sketch": grad[1],
            "param_change": param_change[0],
            "param_change_sketch": param_change[1],
            "ema_change": ema_change[0], "ema_change_sketch": ema_change[1]}
