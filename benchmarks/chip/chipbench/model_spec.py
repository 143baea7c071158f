"""A configuration file's sizes, and the parameter tree they make.

The configuration files hold a model's published ``config.json`` keys
(Hugging Face names) and a ``run`` section with how the job runs. This
module reads the sizes from them and lays out the parameter tree in the
form the trainer takes: one leaf per weight, the decoder layers stacked
on a leading axis. The benchmark makes the weights itself, from the
seed, so that the plain reference can make the same weights again
without taking anything from the program.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    dtype: str

    @property
    def param_count(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.heads * hd * 2 + d * self.kv_heads * hd * 2 + 2 * hd
        mlp = 3 * d * self.d_ff
        return (self.vocab * d + d
                + self.layers * (attn + mlp + 2 * d))


def sizes(config: Dict[str, Any]) -> Sizes:
    """The sizes of a Qwen3-style dense decoder from its config keys."""
    if config.get("model_type") != "qwen3":
        raise ValueError(f"model_type {config.get('model_type')!r}: only "
                         f"qwen3 (dense, qk-norm, SwiGLU) is laid out here")
    if not config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("expected tied embeddings and a SiLU-gated MLP")
    if config.get("attention_bias") or config.get("use_sliding_window"):
        raise ValueError("attention biases and sliding windows are not "
                         "laid out here")
    return Sizes(layers=config["num_hidden_layers"],
                 d_model=config["hidden_size"],
                 heads=config["num_attention_heads"],
                 kv_heads=config["num_key_value_heads"],
                 head_dim=config["head_dim"],
                 d_ff=config["intermediate_size"],
                 vocab=config["vocab_size"],
                 rope_theta=float(config["rope_theta"]),
                 eps=float(config["rms_norm_eps"]),
                 dtype=config["torch_dtype"])


def leaf_shapes(s: Sizes) -> Dict[str, Any]:
    """Nested dict of leaf shapes; layer leaves lead with ``layers``."""
    L, d, hd, f = s.layers, s.d_model, s.head_dim, s.d_ff
    return {
        "embed": {"embedding": (s.vocab, d)},
        "final_norm": {"scale": (d,)},
        "seg_dense": {
            "ln1": {"scale": (L, d)},
            "attn": {"wq": {"w": (L, d, s.heads * hd)},
                     "wk": {"w": (L, d, s.kv_heads * hd)},
                     "wv": {"w": (L, d, s.kv_heads * hd)},
                     "wo": {"w": (L, s.heads * hd, d)},
                     "q_norm": {"scale": (L, hd)},
                     "k_norm": {"scale": (L, hd)}},
            "ln2": {"scale": (L, d)},
            "mlp": {"w_up": {"w": (L, d, f)},
                    "w_down": {"w": (L, f, d)},
                    "w_gate": {"w": (L, d, f)}},
        },
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


GRID_EXPONENT = 12   # weights are k * 2**-12 with k uniform in [-128, 128)


def init_fn(s: Sizes):
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
