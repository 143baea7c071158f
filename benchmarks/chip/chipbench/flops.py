"""Operations and bytes from shapes: the yardstick's arithmetic.

Model FLOPs belong to the architecture: ``train_flops_per_token``
dispatches on ``s.model_type`` to ``archs/<model_type>.py``, which
copies its part of the program's analytic FLOP model
(``repro.analysis.perfmodel``), so that no change to the program can
change how the benchmark counts. What every architecture shares stays
here: the causal mask's average keys per query and the masked reduce's
bytes. Conventions: a matrix product is 2*M*N*K; causal attention counts
the average number of keys a query attends to; a training step is the
forward pass plus twice it for the backward. Recomputation under remat
is not counted: these are model FLOPs, the work the step requires.
"""
from __future__ import annotations

from chipbench import spec


def avg_kv(seq: int) -> float:
    """Mean number of keys a query attends to under a causal mask."""
    return (seq + 1) / 2.0


def train_flops_per_token(s, seq: int) -> float:
    """Forward + backward model FLOPs per trained token at ``seq``."""
    return spec.arch(s.model_type).train_flops_per_token(s, seq)


def backup_reduce_bytes(w_local: int, params: int) -> float:
    """HBM bytes of one in-shard masked reduce: the [W_local, P] f32
    stack read once and the [P] f32 mean written once."""
    return 4.0 * params * (w_local + 1)
