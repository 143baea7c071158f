"""SPMD execution engine: coordination strategies over a real device mesh.

Every path built in PRs 1–3 executes the paper's W workers as a *loop
index* on one device: the global batch is one array, per-worker gradients
are either implicit (the mask-weighted loss trick) or a stacked
``[W, ...]`` pytree. This module is the execution substrate the paper
actually describes — N workers computing gradients **in parallel on
distinct devices**:

* the W coordination workers are laid out over the mesh's ``'data'``
  axis (``W % mesh_data == 0``; each shard owns ``W / mesh_data``
  contiguous workers and only *their* rows of the global batch);
* each shard computes its local workers' mean gradients sequentially
  (``lax.map`` — one worker's activation memory at a time, exactly the
  per-machine footprint of the paper's setup);
* the paper's Alg. 4 line 7 ``(1/N) * sum_{selected} G_w`` is realized
  as a **collective**: the in-shard masked reduce is the
  ``kernels.backup_reduce`` Pallas kernel (or the jnp reference) over
  the local ``[W_local, P]`` stack, followed by one ``psum`` over
  ``'data'`` — at no point does a stacked ``[W, ...]`` gradient tree
  exist on any single device;
* the optimizer + EMA apply to the (replicated) aggregated gradient
  outside the shard_map, so checkpoints keep the exact on-disk format
  of the simulated backend.

The mask itself stays host-planned (the ``StragglerSimulator`` /
``CoordinationStrategy.select`` contract is unchanged — masks are *data*
to the engine), so the mesh run is comparable step-for-step with the
single-device simulated run: parity is allclose, not bit-exact, because
the sim backend differentiates the mask-weighted global loss while the
engine sums explicit per-worker gradients (the same value in different
floating-point association).

**Tensor parallelism over the ``'model'`` axis**: with ``mesh_model > 1``
the engine shards model parameters, optimizer state and EMA over the
mesh's second axis (PartitionSpecs from ``distributed.sharding.tp_plan``
/ ``tp_param_specs`` / ``tp_state_specs``), and each worker's gradient
is computed **tensor-parallel inside its 'data' shard**: the model runs
with a per-shard config (heads / hidden width divided by ``mesh_model``)
and the Megatron f/g collectives of ``repro.distributed.tp`` supply the
explicit psums over ``'model'`` at the contracted dims (attention out,
FFN down-projection, vocab-sharded embedding/cross-entropy). The masked
aggregation then runs ON the sharded trees: each ``(data, model)`` shard
kernel-reduces its local ``[W_local, P_local]`` flatten and one psum
over ``'data'`` completes Alg. 4 line 7 — params, opt state, gradients
and EMA never leave their shard during a step (gather/scatter happens
only at checkpoint save/restore, which keeps checkpoints interchangeable
with replicated and simulated runs). Groups that cannot shard (config
indivisible by ``mesh_model``, biased row-parallel layers, non-
transformer families) stay replicated per the plan; when nothing shards
the axis is carried exactly as in the pre-TP engine.

Chunking composes: ``build_spmd_chunk_step`` wraps the step in the same
``lax.scan`` as the single-device chunked loop — the scan carries the
*sharded* param/opt/EMA trees, so one dispatch covers K steps across the
whole mesh. See docs/spmd.md.
"""
from __future__ import annotations

import contextlib
import functools
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import ema as ema_lib
from repro.distributed import sharding as sharding_lib
from repro.distributed import tp
from repro.kernels.bucketed_reduce import reduce_then_psum
from repro.launch.mesh import make_host_mesh
from repro.optim import optimizers as opt_lib

WORKER_AXIS = "data"
MODEL_AXIS = "model"


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the varying-manual-axes check (the
    bodies psum explicitly)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Mesh construction / layout validation
# ---------------------------------------------------------------------------


def build_mesh(exec_cfg) -> Mesh:
    """The engine's ('data', 'model') worker mesh from an ExecutionConfig."""
    need = exec_cfg.num_devices
    have = len(jax.devices())
    if need > have:
        raise ValueError(
            f"execution backend 'spmd' needs mesh_data*mesh_model = {need} "
            f"devices but only {have} present; on CPU hosts force devices "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count={need}")
    return make_host_mesh(exec_cfg.mesh_data, exec_cfg.mesh_model)


def validate_layout(num_workers: int, global_batch: int,
                    mesh_data: int) -> int:
    """Checks W/B divisibility over the data axis; returns W_local."""
    if mesh_data < 1:
        raise ValueError(f"mesh_data must be >= 1 (got {mesh_data})")
    if num_workers % mesh_data:
        raise ValueError(
            f"spmd engine maps workers onto the '{WORKER_AXIS}' axis: "
            f"total_workers ({num_workers}) must be divisible by "
            f"mesh_data ({mesh_data})")
    if global_batch % num_workers:
        raise ValueError(
            f"global_batch ({global_batch}) must be divisible by "
            f"total_workers ({num_workers})")
    return num_workers // mesh_data


def _auto_use_kernel(use_kernel: Optional[bool]) -> bool:
    """Default reduce implementation: the Pallas kernel where it compiles
    natively (TPU), the jnp dot elsewhere — interpret-mode Pallas is pure
    overhead on CPU/GPU (measured in BENCH_spmd; docs/spmd.md)."""
    if use_kernel is not None:
        return use_kernel
    return jax.default_backend() == "tpu"


def validate_grad_batch(grad_batch: int, w_local: int) -> int:
    """Resolve ``ExecutionConfig.grad_batch`` against the local worker
    count; returns the effective batch size.

    ``0`` (the default) batches ALL local workers through one ``vmap`` —
    the fast path whenever activation memory allows, since every worker's
    forward/backward fuses into one program with no inner loop. ``1``
    recovers the sequential ``lax.map`` (one worker's activations live at
    a time — the per-machine footprint of the paper's setup). Any other
    value microbatches: groups of ``grad_batch`` workers are vmapped and
    the groups run sequentially, so it must divide ``W_local``.
    """
    if grad_batch < 0:
        raise ValueError(
            f"grad_batch: expected a non-negative worker-batch size, got "
            f"{grad_batch} (0 = vmap all local workers, 1 = sequential "
            f"lax.map, k = microbatches of k workers)")
    if grad_batch and w_local % grad_batch:
        divisors = [d for d in range(1, w_local + 1) if w_local % d == 0]
        raise ValueError(
            f"grad_batch: {grad_batch} does not divide the per-shard "
            f"worker count W_local={w_local} (total_workers / mesh_data); "
            f"valid values here: 0 (vmap all) or one of {divisors}")
    return grad_batch or w_local


# ---------------------------------------------------------------------------
# Stacked-gradient flatten/unflatten (the kernel's [W_local, P] view)
# ---------------------------------------------------------------------------


def flatten_stacked(tree: Any) -> Tuple[jnp.ndarray, Tuple]:
    """[W, ...] pytree -> ([W, P] f32, spec) with P = total param count."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = tuple(l.shape[1:] for l in leaves)
    dtypes = tuple(l.dtype for l in leaves)
    flat = jnp.concatenate(
        [l.reshape((l.shape[0], -1)).astype(jnp.float32) for l in leaves],
        axis=1)
    return flat, (treedef, shapes, dtypes)


def unflatten_vector(vec: jnp.ndarray, spec: Tuple) -> Any:
    """[P] f32 -> pytree with the original shapes/dtypes."""
    treedef, shapes, dtypes = spec
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    offsets = np.cumsum([0] + sizes)
    leaves = [
        vec[offsets[i]:offsets[i + 1]].reshape(shapes[i]).astype(dtypes[i])
        for i in range(len(shapes))]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Per-worker loss (paper semantics: each worker's own mini-batch mean)
# ---------------------------------------------------------------------------


def make_worker_loss(model) -> Callable:
    """loss(params, worker_batch) -> (scalar, (mean_loss, aux)).

    Mirrors ``train_step.make_loss_fn``'s per-example loss (token-validity
    masking, vlm prefix padding) but at single-worker granularity: the
    worker's gradient is the gradient of ITS mini-batch mean — including
    its own aux loss, as a real worker machine would compute it. (The sim
    backend instead adds one global-batch aux term; the two agree
    whenever aux == 0, i.e. all non-MoE models.)
    """

    def loss_fn(params, batch):
        per_tok, aux = model.per_token_loss(params, batch)
        labels = batch["labels"]
        if per_tok.shape[1] != labels.shape[1]:       # vlm prefix positions
            pad = per_tok.shape[1] - labels.shape[1]
            labels = jnp.concatenate(
                [jnp.full((labels.shape[0], pad), -1, labels.dtype), labels],
                1)
        valid = (labels >= 0).astype(jnp.float32)
        per_ex = (jnp.sum(per_tok * valid, axis=-1)
                  / jnp.maximum(jnp.sum(valid, axis=-1), 1.0))
        mean_loss = jnp.mean(per_ex)
        return mean_loss + aux, (mean_loss, aux)

    return loss_fn


# ---------------------------------------------------------------------------
# The engine step
# ---------------------------------------------------------------------------


def resolve_tp(model_cfg, mesh: Mesh) -> sharding_lib.TPPlan:
    """The TP plan for a mesh ('model' axis size) + model config pair.

    Warns when ``mesh_model > 1`` was requested but no parameter group can
    shard (indivisible config, biased layers, non-transformer family, or
    a config-less model override) — the axis is then carried (replicated),
    the pre-TP engine semantics."""
    names = dict(zip(mesh.axis_names, mesh.devices.shape))
    mesh_model = names.get(MODEL_AXIS, 1)
    plan = sharding_lib.tp_plan(model_cfg, mesh_model)
    if mesh_model > 1 and not plan.any:
        warnings.warn(
            f"mesh_model={mesh_model} but no parameter group is shardable "
            f"for this model (see sharding.tp_plan: divisibility, biases, "
            f"family); the '{MODEL_AXIS}' axis will be carried (replicated)",
            stacklevel=2)
    return plan


def _params_template(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def build_spmd_step(model, optimizer: opt_lib.Optimizer, mesh: Mesh, *,
                    num_workers: int, n_aggregate: int,
                    ema_decay: float = 0.0, clip_norm: float = 0.0,
                    use_kernel: Optional[bool] = None,
                    block: Optional[int] = None, grad_batch: int = 0,
                    bucket_size: int = 0, model_cfg=None) -> Callable:
    """Mesh twin of ``train_step.build_train_step`` — same signature:

        step(params, opt_state, ema, step, batch, mask)
            -> (params, opt_state, ema, metrics)

    ``batch`` rows are worker-contiguous (the data-pipeline layout), so
    sharding axis 0 over ``'data'`` gives each shard exactly its local
    workers' rows; ``mask`` is the host-planned [W] selection, sharded to
    [W_local] per shard. Per-worker gradients are BATCHED per
    ``grad_batch`` (0 = one ``vmap`` over all local workers — the fast
    path; 1 = the sequential ``lax.map``, one worker's activations at a
    time; k = microbatches of k vmapped workers run sequentially).
    Aggregation is the fused bucketed reduce-then-psum
    (``kernels.bucketed_reduce``): the in-shard masked reduce (Pallas
    ``backup_reduce`` or the jnp dot, per ``use_kernel``) runs per
    ``bucket_size`` lanes and each bucket's ``psum`` over ``'data'`` is
    issued as soon as that bucket reduces, with the step's monitoring
    scalars packed into the last bucket — one collective per bucket
    covers gradient + metrics. Optimizer/EMA run outside the shard_map.

    With ``model_cfg`` given and a non-trivial TP plan (mesh 'model' axis
    > 1, shardable groups), params/opt/EMA enter SHARDED over 'model':
    the shard_map body sees local parameter slices, the per-worker loss
    runs the per-shard model (heads / d_ff divided) under the
    ``repro.distributed.tp`` context that inserts the f/g psums, and the
    aggregated gradient leaves the shard_map still sharded — the
    optimizer and EMA then apply shard-wise under GSPMD (elementwise ops
    preserve the sharding), so no resharding round-trip exists anywhere
    in the step.
    """
    names = dict(zip(mesh.axis_names, mesh.devices.shape))
    mesh_data = names[WORKER_AXIS]
    if num_workers % mesh_data:
        raise ValueError(
            f"total_workers ({num_workers}) must be divisible by the "
            f"'{WORKER_AXIS}' axis size ({mesh_data})")
    w_local = num_workers // mesh_data
    gb = validate_grad_batch(grad_batch, w_local)
    use_kernel = _auto_use_kernel(use_kernel)
    plan = resolve_tp(model_cfg, mesh)
    if plan.any:
        from repro.models import get_model
        local_model = get_model(sharding_lib.tp_local_model_cfg(model_cfg, plan))
        worker_loss = make_worker_loss(local_model)
        param_specs = sharding_lib.tp_param_specs(plan, _params_template(model))
        tp_ctx = tp.TPContext(MODEL_AXIS, plan.attn, plan.ffn, plan.vocab)
    else:
        worker_loss = make_worker_loss(model)
        param_specs = P()                       # replicated (pytree prefix)
        tp_ctx = None

    def shard_grads(batch, mask, params):
        # batch: local rows [b_local, ...]; mask: [W_local]; params: full
        # when replicated, the local 'model'-axis slices under a TP plan
        def reshape(x):
            return x.reshape((w_local, x.shape[0] // w_local) + x.shape[1:])

        shards = jax.tree_util.tree_map(reshape, batch)

        def one_worker(worker_batch):
            with jax.named_scope("grad"):
                (_, (mean_loss, aux)), g = jax.value_and_grad(
                    worker_loss, has_aux=True)(params, worker_batch)
            return g, mean_loss, aux

        # per-worker gradients, batched per grad_batch: the full vmap is
        # one fused program with no inner loop (the fast path); lax.map
        # keeps one worker's activations live at a time — the per-machine
        # memory footprint of the paper's setup; k-sized microbatches
        # interpolate. The tp context is entered here (inside the traced
        # body) so the f/g psum hooks fire exactly for engine-built
        # computations.
        with tp.tensor_parallel(tp_ctx) if tp_ctx else contextlib.nullcontext():
            if gb == w_local:
                grads, losses, auxes = jax.vmap(one_worker)(shards)
            elif gb == 1:
                grads, losses, auxes = jax.lax.map(one_worker, shards)
            else:
                groups = jax.tree_util.tree_map(
                    lambda x: x.reshape((w_local // gb, gb) + x.shape[1:]),
                    shards)
                grads, losses, auxes = jax.lax.map(
                    lambda g: jax.vmap(one_worker)(g), groups)
                grads, losses, auxes = jax.tree_util.tree_map(
                    lambda x: x.reshape((w_local,) + x.shape[2:]),
                    (grads, losses, auxes))
        mf = mask.astype(jnp.float32)
        # fused bucketed reduce-then-psum (kernels.bucketed_reduce): the
        # in-shard masked reduce runs per bucket and each bucket's psum
        # over 'data' is issued immediately, with the two monitoring
        # scalars riding the last bucket — ceil(P/bucket) collectives
        # (ONE by default) cover Alg. 4 line 7 plus the metrics. Losses
        # are replicated over 'model' (the CE ends in psums), so only
        # the 'data' reduction is collective.
        with jax.named_scope("grad_stack"):
            flat, spec = flatten_stacked(grads)     # [W_local, P_local] f32
        tail = jnp.stack([jnp.sum(losses * mf), jnp.sum(auxes)])
        with jax.named_scope("reduce"):
            red, tail = reduce_then_psum(
                flat, mask, n_aggregate, axis_name=WORKER_AXIS,
                bucket=bucket_size, tail=tail, use_kernel=use_kernel,
                block=block)
        agg = unflatten_vector(red, spec)
        sel = tail[0] / n_aggregate
        aux = tail[1] / num_workers
        return agg, sel, aux

    mapped = _shard_map(
        shard_grads, mesh,
        in_specs=(P(WORKER_AXIS), P(WORKER_AXIS), param_specs),
        out_specs=(param_specs, P(), P()))

    def step_fn(params, opt_state, ema_state, step, batch, mask):
        grads, sel, aux = mapped(batch, mask, params)
        frac = jnp.sum(mask.astype(jnp.float32)) / n_aggregate
        metrics = {"loss": sel / jnp.maximum(frac, 1e-6), "aux_loss": aux}
        with jax.named_scope("optimizer"):
            if clip_norm > 0:
                # global_norm sums over all leaves; on sharded trees GSPMD
                # lowers the per-leaf reductions to one small all-reduce
                grads, gnorm = opt_lib.clip_by_global_norm(grads, clip_norm)
                metrics["grad_norm"] = gnorm
            new_params, new_opt, stats = optimizer.apply(params, grads,
                                                         opt_state, step)
        metrics.update(stats)
        if ema_decay > 0:
            with jax.named_scope("ema"):
                ema_state = ema_lib.update(ema_state, new_params, ema_decay)
        return new_params, new_opt, ema_state, metrics

    return step_fn


def build_spmd_chunk_step(model, optimizer: opt_lib.Optimizer, mesh: Mesh,
                          **step_kwargs) -> Callable:
    """Mesh twin of the host-mask ``build_chunk_step``: one ``lax.scan``
    dispatch covers K steps across the whole mesh.

        chunk(params, opt, ema, step0, batches [K, B, ...], masks [K, W])
            -> (params, opt, ema, metrics {k: [K]})

    The scan body is the unmodified ``build_spmd_step`` function, so
    chunking never changes the mesh semantics — only the dispatch count.
    """
    step_fn = build_spmd_step(model, optimizer, mesh, **step_kwargs)

    def scan_steps(params, opt_state, ema_state, step0, batches, masks):
        def body(carry, xs):
            p, o, e, step = carry
            batch, mask = xs
            p, o, e, m = step_fn(p, o, e, step, batch, mask)
            return (p, o, e, step + 1), m

        (p, o, e, _), ms = jax.lax.scan(
            body, (params, opt_state, ema_state, step0), (batches, masks))
        return p, o, e, ms

    return scan_steps


# ---------------------------------------------------------------------------
# Jitted entry points (what the Trainer installs)
# ---------------------------------------------------------------------------


def _replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def state_shardings(model, optimizer, mesh: Mesh, *, ema_decay: float = 0.0,
                    model_cfg=None) -> Tuple[Any, Any, Any]:
    """(params, opt_state, ema) NamedSharding trees for the engine's jit.

    Replicated trees without a TP plan (the pre-TP engine contract);
    under a plan, params shard per ``sharding.tp_param_specs`` and the
    optimizer/EMA state — whatever its tree structure — inherits the
    matching parameter's spec by path suffix (``sharding.tp_state_specs``).
    (The plan/templates are also derived inside ``build_spmd_step``; both
    are cheap eval_shape/spec walks that run once per Trainer build.)
    """
    plan = sharding_lib.tp_plan(
        model_cfg,
        dict(zip(mesh.axis_names, mesh.devices.shape)).get(MODEL_AXIS, 1))
    rep = _replicated(mesh)
    if not plan.any:
        return rep, rep, rep
    params_t = _params_template(model)
    opt_t = jax.eval_shape(optimizer.init, params_t)

    def named(spec_tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, P))

    psh = named(sharding_lib.tp_param_specs(plan, params_t))
    osh = named(sharding_lib.tp_state_specs(plan, opt_t))
    if ema_decay > 0:
        ema_t = jax.eval_shape(ema_lib.init, params_t)
        esh = named(sharding_lib.tp_state_specs(plan, ema_t))
    else:
        esh = rep                               # ema arg is None
    return psh, osh, esh


def make_train_step(model, optimizer, mesh: Mesh,
                    **step_kwargs) -> Callable:
    """Jitted per-step engine, drop-in for the Trainer's ``train_step``:
    step/mask replicated, batch rows sharded over 'data', and params/
    opt/ema replicated — or sharded over 'model' under a TP plan. The
    state out_shardings are pinned to the in_shardings, so the sharded
    carry round-trips the Trainer loop without resharding."""
    psh, osh, esh = state_shardings(
        model, optimizer, mesh,
        ema_decay=step_kwargs.get("ema_decay", 0.0),
        model_cfg=step_kwargs.get("model_cfg"))
    rep = _replicated(mesh)
    bsh = NamedSharding(mesh, P(WORKER_AXIS))
    return jax.jit(build_spmd_step(model, optimizer, mesh, **step_kwargs),
                   in_shardings=(psh, osh, esh, rep, bsh, rep),
                   out_shardings=(psh, osh, esh, rep),
                   donate_argnums=(0, 1, 2))


def make_chunk_step(model, optimizer, mesh: Mesh,
                    **step_kwargs) -> Callable:
    """Jitted K-step engine, drop-in for the Trainer's ``chunk_step``:
    stacked batches [K, B, ...] shard axis 1 (the batch rows) over 'data';
    the scan carries the (possibly 'model'-sharded) state trees."""
    psh, osh, esh = state_shardings(
        model, optimizer, mesh,
        ema_decay=step_kwargs.get("ema_decay", 0.0),
        model_cfg=step_kwargs.get("model_cfg"))
    rep = _replicated(mesh)
    bsh = NamedSharding(mesh, P(None, WORKER_AXIS))
    return jax.jit(
        build_spmd_chunk_step(model, optimizer, mesh, **step_kwargs),
        in_shardings=(psh, osh, esh, rep, bsh, rep),
        out_shardings=(psh, osh, esh, rep),
        donate_argnums=(0, 1, 2))
