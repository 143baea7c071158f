"""A configuration file's sizes and parameter tree, by architecture.

The configuration files hold a model's published ``config.json`` keys
(Hugging Face names) and a ``run`` section with how the job runs. What
belongs to one architecture lives in ``archs/<model_type>.py``, found by
the file's ``model_type`` (``spec.arch``), so that a configuration of a
new architecture is added by adding files. Such a file brings:

    sizes(config)       a frozen, hashable dataclass of the sizes, with
                        at least ``model_type``, ``vocab``, ``dtype`` and
                        ``param_count``
    leaf_shapes(s)      the parameter tree as the trainer takes it, one
                        shape per weight; the leaves under each
                        ``seg_<kind>`` key lead with that segment's
                        layer count
    program_model(m, s) the program's ``ModelConfig`` ``m``, as the
                        training CLI built it, with this file's sizes;
                        raises where the program's model is another
    embed, segments, head_loss
                        the plain reference's model (``reference.py``
                        says what each takes)
    train_flops_per_token(s, seq)
                        model FLOPs of one trained token
                        (``flops.py``)
    TINY                the config keys a CPU test cuts (``tiny.py``)

What stays here is generic: the dispatch, the weights made from the
seed over any layout (``init_fn``), and the comparison of two layouts.
The benchmark makes the weights itself, from the seed, so that the
plain reference can make the same weights again without taking
anything from the program.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from chipbench import spec

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def sizes(config: Dict[str, Any], bench_dir: str = spec.BENCH_DIR):
    """The sizes of the configuration's architecture
    (``archs/<model_type>.py``)."""
    return spec.arch(config.get("model_type"), bench_dir).sizes(config)


def leaf_shapes(s) -> Dict[str, Any]:
    """Nested dict of leaf shapes; segment leaves lead with its layers."""
    return spec.arch(s.model_type).leaf_shapes(s)


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


GRID_EXPONENT = 12   # weights are k * 2**-12 with k uniform in [-128, 128)


def init_fn(s):
    """key -> parameters: every matrix uniform on the grid k * 2**-12,
    k in [-128, 128) (std 0.018, near the published
    ``initializer_range`` of 0.02), every norm scale 1. Each leaf draws
    its own ``fold_in`` of the key.

    Every value is exact in bfloat16 and made by integer operations and
    one multiply by a power of two, so any program that makes the
    weights, fused however XLA likes, makes the same bits: the run's
    weights, the weights it makes again to measure their change, and
    the reference's. (A normal draw goes through a transcendental whose
    rounding changes with fusion, and one ulp of bfloat16 is more than
    the EMA moves in three steps.)"""
    shapes = leaf_shapes(s)
    dtype = DTYPES[s.dtype]
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=_is_shape)[0]]
    scale = 2.0 ** -GRID_EXPONENT

    def init(key):
        leaves, treedef = jax.tree_util.tree_flatten(shapes, is_leaf=_is_shape)
        out = []
        for i, (path, shape) in enumerate(zip(paths, leaves)):
            if path.endswith("['scale']"):
                out.append(jnp.ones(shape, dtype))
            else:
                bits = jax.random.bits(jax.random.fold_in(key, i), shape,
                                       jnp.uint8)
                k = bits.astype(jnp.int32) - 128
                out.append((k.astype(jnp.float32) * scale).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return init


def tree_signature(tree) -> Tuple:
    """(path, shape, dtype) of every leaf: what two layouts must share."""
    return tuple((jax.tree_util.keystr(p), tuple(x.shape), str(x.dtype))
                 for p, x in jax.tree_util.tree_flatten_with_path(tree)[0])
