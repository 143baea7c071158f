"""The one traffic generator: training batches from a traffic file.

A traffic file (``traffic/<name>.json``) states the job: ``workers``
aggregated per step (N), ``backups`` launched beside them (b),
``rows_per_worker`` and ``seq_len`` of each worker's mini-batch, the
token distribution, and ``steps_per_block``, how many steps the window
hands the trainer per call. Rows are laid out worker after worker, as
the trainer expects: worker w owns rows [w*R, (w+1)*R).

Step i's batch depends on (seed, i) alone, so the reference makes the
same batches again from the seed.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import numpy as np


@dataclasses.dataclass(frozen=True)
class Traffic:
    workers: int
    backups: int
    rows_per_worker: int
    seq_len: int
    steps_per_block: int

    @property
    def total_workers(self) -> int:
        return self.workers + self.backups

    @property
    def rows(self) -> int:
        return self.total_workers * self.rows_per_worker

    @property
    def tokens_per_worker(self) -> int:
        return self.rows_per_worker * self.seq_len


def traffic(spec: Dict[str, Any]) -> Traffic:
    if spec.get("tokens", "uniform") != "uniform":
        raise ValueError(f"token distribution {spec['tokens']!r}: only "
                         f"'uniform' is generated")
    t = Traffic(workers=int(spec["workers"]), backups=int(spec["backups"]),
                rows_per_worker=int(spec["rows_per_worker"]),
                seq_len=int(spec["seq_len"]),
                steps_per_block=int(spec["steps_per_block"]))
    if min(t.workers, t.rows_per_worker, t.seq_len, t.steps_per_block) < 1 \
            or t.backups < 0:
        raise ValueError(f"traffic {spec}: sizes must be positive")
    return t


def seed_words(seed: int):
    """Any whole number, negative or past 64 bits, as entropy words."""
    s = int(seed)
    return [s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF, 1 if s < 0 else 0,
            abs(s) >> 64]


def derived_seed(seed: int, tag: int, bits: int = 31) -> int:
    """A ``bits``-bit seed for one consumer (``tag``) of ``seed``."""
    ss = np.random.SeedSequence(seed_words(seed) + [tag])
    return int(ss.generate_state(1, np.uint64)[0] >> np.uint64(64 - bits))


class _Position:
    """What the trainer reads of its data pipeline's state."""

    def __init__(self):
        self.step = 0


class Feed:
    """Batches of uniform random tokens, next-token labels.

    Stands in the trainer's ``pipeline`` slot: ``next()`` returns step
    ``state.step``'s batch and advances, as the program's pipeline does.
    """

    def __init__(self, t: Traffic, vocab: int, seed: int):
        self.t = t
        self.vocab = vocab
        self.words = seed_words(seed)
        self.state = _Position()

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(self.words + [0xFEED, int(step)])
        seq = rng.integers(0, self.vocab, size=(self.t.rows, self.t.seq_len + 1),
                           dtype=np.int32)
        return {"tokens": seq[:, :-1], "labels": seq[:, 1:]}

    def next(self) -> Dict[str, np.ndarray]:
        b = self.batch(self.state.step)
        self.state.step += 1
        return b
