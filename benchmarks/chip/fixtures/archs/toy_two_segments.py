"""A toy architecture of two segments: a test-only architecture.

The shape a mixture-of-experts decoder brings to the plain reference:
``seg_dense`` (a residual SiLU MLP) and then ``seg_moe`` (a softmax
mixture of expert MLPs that returns a load-balancing auxiliary term
with its output), an untied ``lm_head``, float32 parameters.
``test_chipbench_reference.py`` checks the reference's layer-by-layer
gradient against ``jax.grad`` of the whole loss on it.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench import spec

qwen3 = spec.arch("qwen3")
HIGHEST = jax.lax.Precision.HIGHEST
TINY: dict = {}


@dataclasses.dataclass(frozen=True)
class Sizes:
    model_type: str
    vocab: int
    d_model: int
    d_ff: int
    dense_layers: int
    moe_layers: int
    experts: int
    aux_coef: float
    eps: float
    dtype: str

    @property
    def param_count(self) -> int:
        d, f, e = self.d_model, self.d_ff, self.experts
        return (2 * self.vocab * d + d
                + self.dense_layers * (d + 2 * d * f)
                + self.moe_layers * (d + d * e + 2 * e * d * f))


def sizes(config):
    return Sizes(model_type=config["model_type"], vocab=config["vocab_size"],
                 d_model=config["hidden_size"],
                 d_ff=config["intermediate_size"],
                 dense_layers=config["first_k_dense_replace"],
                 moe_layers=(config["num_hidden_layers"]
                             - config["first_k_dense_replace"]),
                 experts=config["n_routed_experts"],
                 aux_coef=float(config["aux_loss_alpha"]),
                 eps=float(config["rms_norm_eps"]),
                 dtype=config["torch_dtype"])


def leaf_shapes(s: Sizes):
    d, f, e, L1, L2 = (s.d_model, s.d_ff, s.experts, s.dense_layers,
                       s.moe_layers)
    return {
        "embed": {"embedding": (s.vocab, d)},
        "final_norm": {"scale": (d,)},
        "lm_head": {"w": (d, s.vocab)},
        "seg_dense": {"ln": {"scale": (L1, d)},
                      "mlp": {"w_in": {"w": (L1, d, f)},
                              "w_out": {"w": (L1, f, d)}}},
        "seg_moe": {"ln": {"scale": (L2, d)},
                    "router": {"w": (L2, d, e)},
                    "experts": {"w_in": {"w": (L2, e, d, f)},
                                "w_out": {"w": (L2, e, f, d)}}},
    }


def program_model(model, s: Sizes):
    raise ValueError("the toy architecture has no program model")


def _mm(eq, a, b, cast):
    return jnp.einsum(eq, cast(a), cast(b), precision=HIGHEST)


def dense_layer(p, x, s: Sizes, cast):
    h = qwen3.rms(x, p["ln"]["scale"], s.eps)
    m = p["mlp"]
    up = jax.nn.silu(_mm("rnd,df->rnf", h, m["w_in"]["w"], cast))
    return x + _mm("rnf,fd->rnd", up, m["w_out"]["w"], cast)


def moe_layer(p, x, s: Sizes, cast):
    h = qwen3.rms(x, p["ln"]["scale"], s.eps)
    gates = jax.nn.softmax(_mm("rnd,de->rne", h, p["router"]["w"], cast), -1)
    ex = p["experts"]
    up = jax.nn.silu(_mm("rnd,edf->rnef", h, ex["w_in"]["w"], cast))
    out = _mm("rnef,efd->rned", up, ex["w_out"]["w"], cast)
    load = jnp.mean(gates, axis=(0, 1))
    aux = s.aux_coef * s.experts * jnp.sum(load * load)
    return x + jnp.einsum("rne,rned->rnd", gates, out), aux


def segments(s: Sizes):
    return [("seg_dense", dense_layer), ("seg_moe", moe_layer)]


def embed(p, tokens, s: Sizes):
    return jnp.take(p["embed"]["embedding"], tokens, axis=0)


def head_loss(p, x, labels, s: Sizes, cast):
    h = qwen3.rms(x, p["final_norm"]["scale"], s.eps)
    logits = _mm("rnd,dv->rnv", h, p["lm_head"]["w"], cast)
    return qwen3.cross_entropy(logits, labels)


def train_flops_per_token(s: Sizes, seq: int) -> float:
    d, f, e = s.d_model, s.d_ff, s.experts
    layers = (s.dense_layers * 4 * d * f
              + s.moe_layers * (2 * d * e + 4 * e * d * f))
    return 3.0 * (layers + 2 * d * s.vocab)
