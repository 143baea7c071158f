"""Compile each cell's timed step for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python3 benchmarks/chip/compile_check.py [<workload> ...]

Builds the cell's trainer exactly as a run does (on the CPU, which is
what is attached), then compiles its per-step program for the chips of
a described ``v5e:2x2``: on one chip for the sim backend, on a 4-chip
'data' mesh, with the native Pallas reduce, for the SPMD engine. Prints
``memory_analysis`` per chip and what the compiled program holds
(kernels, all-reduces). The compiler refuses what would not fit.
Nothing runs, so this says nothing about time or results.
"""
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from chipbench import feed, harness, model_spec, spec  # noqa: E402


def _shapes(tree, sharding):
    """``tree`` as abstract arrays placed by ``sharding``: one for every
    leaf, or a tree of them."""
    if isinstance(sharding, jax.sharding.Sharding):
        sharding = jax.tree_util.tree_map(lambda _: sharding, tree)
    return jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding)


def compile_cell(cell: spec.Cell, topo) -> str:
    from repro.core import ema as ema_lib
    from repro.distributed import spmd_engine
    from repro.kernels import backup_reduce
    from repro.core.straggler import PaperCalibrated
    from repro.train.loop import Trainer
    from repro.train.train_step import build_train_step

    traffic = feed.traffic(cell.traffic)
    cfg = harness._program_config(cell, traffic, 0)
    tr = Trainer(cfg, latency=PaperCalibrated())
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(tr.model.init, key)
    opt = jax.eval_shape(tr.optimizer.init, params)
    ema = jax.eval_shape(ema_lib.init, params)
    kwargs = dict(num_workers=traffic.total_workers,
                  n_aggregate=traffic.workers,
                  ema_decay=cfg.optimizer.ema_decay,
                  clip_norm=cfg.optimizer.clip_global_norm)
    batch = {k: jax.ShapeDtypeStruct((traffic.rows, traffic.seq_len),
                                     jnp.int32)
             for k in ("tokens", "labels")}
    if cfg.execution.backend == "spmd":
        d = cfg.execution.mesh_data
        mesh = Mesh(np.array(topo.devices[:d]).reshape(d, 1),
                    ("data", "model"))
        # the engine picks the native kernel from the attached backend
        # (the CPU here): ask for the chip's path explicitly
        backup_reduce.interpret_mode = lambda interpret=None: False
        step = spmd_engine.make_train_step(
            tr.model, tr.optimizer, mesh, use_kernel=True,
            grad_batch=cfg.execution.grad_batch,
            bucket_size=cfg.execution.bucket_size, model_cfg=cfg.model,
            **kwargs)
        psh, osh, esh = spmd_engine.state_shardings(
            tr.model, tr.optimizer, mesh, ema_decay=cfg.optimizer.ema_decay,
            model_cfg=cfg.model)
        rep = NamedSharding(mesh, P())
        args = (_shapes(params, psh), _shapes(opt, osh), _shapes(ema, esh),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
                _shapes(batch, NamedSharding(mesh, P("data"))),
                jax.ShapeDtypeStruct((traffic.total_workers,), jnp.bool_,
                                     sharding=rep))
    else:
        one = SingleDeviceSharding(topo.devices[0])
        step = jax.jit(build_train_step(tr.model, tr.optimizer, **kwargs),
                       donate_argnums=(0, 1, 2))
        args = (_shapes(params, one), _shapes(opt, one), _shapes(ema, one),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=one),
                _shapes(batch, one),
                jax.ShapeDtypeStruct((traffic.total_workers,), jnp.bool_,
                                     sharding=one))
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    gb = 1e9
    return (f"{cell.name}: per chip arguments {mem.argument_size_in_bytes / gb:.2f} GB"
            f", outputs {mem.output_size_in_bytes / gb:.2f} GB"
            f", aliased {mem.alias_size_in_bytes / gb:.2f} GB"
            f", temp {mem.temp_size_in_bytes / gb:.2f} GB"
            f", arguments + temp "
            f"{(mem.argument_size_in_bytes + mem.temp_size_in_bytes) / gb:.2f} GB"
            f"; Pallas kernels {text.count('tpu_custom_call')}, all-reduces "
            f"{text.count(' all-reduce(') + text.count(' all-reduce-start(')}")


def main(argv):
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = spec.load_benchmark()
    names = argv or [w["name"] for w in bench["workloads"]]
    for name in names:
        print(compile_cell(spec.resolve(name, bench), topo), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
