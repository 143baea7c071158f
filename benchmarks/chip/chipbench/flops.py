"""Operations and bytes from shapes: the yardstick's arithmetic.

The dense-transformer part of the program's analytic FLOP model
(``repro.analysis.perfmodel``: ``_avg_kv``, ``_attn_flops_per_tok``,
``_mlp_flops_per_tok``, ``cell_flops``), copied here so that no change
to the program can change how the benchmark counts. Conventions: a
matrix product is 2*M*N*K; causal attention counts the average number
of keys a query attends to; a training step is the forward pass plus
twice it for the backward. Recomputation under remat is not counted:
these are model FLOPs, the work the step requires.
"""
from __future__ import annotations

from chipbench.model_spec import Sizes


def avg_kv(seq: int) -> float:
    """Mean number of keys a query attends to under a causal mask."""
    return (seq + 1) / 2.0


def attn_flops_per_token(s: Sizes, s_kv: float) -> float:
    d, h, kv, hd = s.d_model, s.heads, s.kv_heads, s.head_dim
    proj = 2 * d * (h * hd) + 2 * 2 * d * (kv * hd) + 2 * (h * hd) * d
    scores = 2 * s_kv * h * hd * 2               # Q K^T and P V
    return proj + scores


def mlp_flops_per_token(s: Sizes) -> float:
    return 2.0 * s.d_model * s.d_ff * 3          # gate, up, down (SwiGLU)


def train_flops_per_token(s: Sizes, seq: int) -> float:
    """Forward + backward model FLOPs per trained token at ``seq``."""
    layers = s.layers * (attn_flops_per_token(s, avg_kv(seq))
                         + mlp_flops_per_token(s))
    head = 2.0 * s.d_model * s.vocab             # logits
    return 3.0 * (layers + head)


def backup_reduce_bytes(w_local: int, params: int) -> float:
    """HBM bytes of one in-shard masked reduce: the [W_local, P] f32
    stack read once and the [P] f32 mean written once."""
    return 4.0 * params * (w_local + 1)
