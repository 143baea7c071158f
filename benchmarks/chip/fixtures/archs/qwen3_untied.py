"""Qwen3 with an untied output head: a test-only architecture.

``test_chipbench_spec.py`` copies it into the ``archs/`` of a copy of
the benchmark, beside a configuration of this ``model_type``, to show
that an architecture enters the benchmark by files alone. Qwen3's
layers, embedding and FLOPs, imported from ``archs/qwen3.py``; the head
is its own ``lm_head``, which the program builds where its model is not
tied.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from chipbench import spec

qwen3 = spec.arch("qwen3")

TINY = qwen3.TINY
segments = qwen3.segments
embed = qwen3.embed
train_flops_per_token = qwen3.train_flops_per_token


@dataclasses.dataclass(frozen=True)
class Sizes(qwen3.Sizes):
    @property
    def param_count(self) -> int:
        return super().param_count + self.d_model * self.vocab


def sizes(config):
    if config["tie_word_embeddings"]:
        raise ValueError("expected an untied output head")
    tied = qwen3.sizes(dict(config, tie_word_embeddings=True))
    return Sizes(**dataclasses.asdict(tied))


def leaf_shapes(s: Sizes):
    return dict(qwen3.leaf_shapes(s), lm_head={"w": (s.d_model, s.vocab)})


def program_model(model, s: Sizes):
    """Qwen3's, checked as Qwen3's, with the head untied."""
    tied = qwen3.program_model(
        dataclasses.replace(model, tie_embeddings=True), s)
    return dataclasses.replace(tied, tie_embeddings=False)


def head_loss(p, x, labels, s: Sizes, cast):
    h = qwen3.rms(x, p["final_norm"]["scale"], s.eps)
    logits = jnp.einsum("rnd,dv->rnv", cast(h), cast(p["lm_head"]["w"]),
                        precision=qwen3.HIGHEST)
    return qwen3.cross_entropy(logits, labels)
