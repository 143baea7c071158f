"""Qwen3, the dense decoder: its sizes, parameter tree, plain reference
and FLOPs (``model_spec.py`` says what an architecture file brings).

The model as published: RMSNorm before attention and MLP, q/k RMSNorm
per head, rotary embedding on the two halves of each head, grouped-query
causal attention, a SiLU-gated MLP, a final RMSNorm and the tied
embedding as output head. Every matrix product of the reference runs at
``Precision.HIGHEST``, so it is float32 on the TPU too; ``cast`` rounds
its operands for the control (``reference.operand_cast``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops

HIGHEST = jax.lax.Precision.HIGHEST

# the config keys a CPU test cuts (chipbench/tiny.py)
TINY = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 512}


@dataclasses.dataclass(frozen=True)
class Sizes:
    model_type: str
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
    """The sizes of a Qwen3 dense decoder from its config keys."""
    if not config["tie_word_embeddings"] or config["hidden_act"] != "silu":
        raise ValueError("expected tied embeddings and a SiLU-gated MLP")
    if config.get("attention_bias") or config.get("use_sliding_window"):
        raise ValueError("attention biases and sliding windows are not "
                         "laid out here")
    return Sizes(model_type=config["model_type"],
                 layers=config["num_hidden_layers"],
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


def program_model(model, s: Sizes):
    """The program's ModelConfig with these sizes; raises where it is
    not a Qwen3 dense decoder with a tied head."""
    model = dataclasses.replace(
        model, num_layers=s.layers, d_model=s.d_model, num_heads=s.heads,
        num_kv_heads=s.kv_heads, head_dim=s.head_dim, d_ff=s.d_ff,
        vocab_size=s.vocab, rope_theta=s.rope_theta, norm_eps=s.eps,
        dtype=s.dtype)
    expect = {"family": "dense", "attention_kind": "gqa", "qk_norm": True,
              "hidden_act": "swiglu", "tie_embeddings": True,
              "use_bias": False, "sliding_window": 0,
              "padded_vocab": s.vocab}
    found = {k: getattr(model, k) for k in expect}
    if found != expect:
        raise ValueError(f"{found} != {expect}")
    return model


# ---------------------------------------------------------------------------
# The plain reference's model
# ---------------------------------------------------------------------------


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """x: [R, S, heads, hd]; rotate the halves (x1, x2) of each head."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p, x, s: Sizes, cast):
    """One decoder block: x [R, S, d] -> x."""
    def mm(eq, a, b):
        return jnp.einsum(eq, cast(a), cast(b), precision=HIGHEST)

    r, n, _ = x.shape
    hd = s.head_dim
    h = rms(x, p["ln1"]["scale"], s.eps)
    a = p["attn"]
    q = mm("rnd,de->rne", h, a["wq"]["w"]).reshape(r, n, s.heads, hd)
    k = mm("rnd,de->rne", h, a["wk"]["w"]).reshape(r, n, s.kv_heads, hd)
    v = mm("rnd,de->rne", h, a["wv"]["w"]).reshape(r, n, s.kv_heads, hd)
    q = rope(rms(q, a["q_norm"]["scale"], s.eps), s.rope_theta)
    k = rope(rms(k, a["k_norm"]["scale"], s.eps), s.rope_theta)
    group = s.heads // s.kv_heads
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = mm("rqhd,rkhd->rhqk", q, k) / math.sqrt(hd)
    causal = np.tril(np.ones((n, n), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = mm("rhqk,rkhd->rqhd", probs, v).reshape(r, n, s.heads * hd)
    x = x + mm("rne,ed->rnd", o, a["wo"]["w"])
    h = rms(x, p["ln2"]["scale"], s.eps)
    m = p["mlp"]
    gate = mm("rnd,df->rnf", h, m["w_gate"]["w"])
    up = mm("rnd,df->rnf", h, m["w_up"]["w"])
    return x + mm("rnf,fd->rnd", jax.nn.silu(gate) * up, m["w_down"]["w"])


def segments(s: Sizes):
    return [("seg_dense", layer)]


def embed(p, tokens, s: Sizes):
    """The rows of the embedding: tokens [R, S] -> x [R, S, d]."""
    return jnp.take(p["embed"]["embedding"], tokens, axis=0)


def head_loss(p, x, labels, s: Sizes, cast):
    """Mean over rows of each row's mean next-token loss, through the
    final norm and the tied embedding."""
    h = rms(x, p["final_norm"]["scale"], s.eps)
    logits = jnp.einsum("rnd,vd->rnv", cast(h), cast(p["embed"]["embedding"]),
                        precision=HIGHEST)
    return cross_entropy(logits, labels)


def cross_entropy(logits, labels):
    """Mean over rows of each row's mean loss of ``labels``."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    label_logit = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jnp.mean(lse - label_logit, axis=-1))


# ---------------------------------------------------------------------------
# FLOPs: the dense part of the program's analytic model
# (``repro.analysis.perfmodel``: ``_attn_flops_per_tok``,
# ``_mlp_flops_per_tok``, ``cell_flops``), copied
# ---------------------------------------------------------------------------


def attn_flops_per_token(s: Sizes, s_kv: float) -> float:
    d, h, kv, hd = s.d_model, s.heads, s.kv_heads, s.head_dim
    proj = 2 * d * (h * hd) + 2 * 2 * d * (kv * hd) + 2 * (h * hd) * d
    scores = 2 * s_kv * h * hd * 2               # Q K^T and P V
    return proj + scores


def mlp_flops_per_token(s: Sizes) -> float:
    return 2.0 * s.d_model * s.d_ff * 3          # gate, up, down (SwiGLU)


def train_flops_per_token(s: Sizes, seq: int) -> float:
    """Forward + backward model FLOPs per trained token at ``seq``."""
    layers = s.layers * (attn_flops_per_token(s, flops.avg_kv(seq))
                         + mlp_flops_per_token(s))
    head = 2.0 * s.d_model * s.vocab             # logits
    return 3.0 * (layers + head)
