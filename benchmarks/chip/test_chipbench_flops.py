"""The yardstick's arithmetic against reckonings done by hand."""
import math
import os
import sys

import jax
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from chipbench import feed, flops, model_spec, peaks, spec  # noqa: E402


def _cell(name):
    cell = spec.resolve(name)
    return model_spec.sizes(cell.config), feed.traffic(cell.traffic)


def test_qwen3_parameter_count():
    s, _ = _cell("qwen3-0.6b.sim1.seq1024x1")
    assert s.param_count == 596_049_920


def test_parameter_count_matches_the_layout():
    s, _ = _cell("qwen3-0.6b.sim1.seq1024x1")
    shapes = model_spec.leaf_shapes(s)
    leaves = jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(shape) for shape in leaves) == s.param_count


# Per token, qwen3-0.6b (d 1024, 16 heads and 8 KV heads of 128, d_ff
# 3072, 28 layers, vocab 151936):
#   q, k, v, o projections  2*1024*2048 + 2*2*1024*1024 + 2*2048*1024
#                           = 12,582,912
#   scores and P V          2 * (S+1)/2 * 16 * 128 * 2
#   SwiGLU MLP              2 * 1024 * 3072 * 3 = 18,874,368
#   output head             2 * 1024 * 151936 = 311,164,928
# A training step is three times the forward.
PROJ, MLP, HEAD = 12_582_912, 18_874_368, 311_164_928


def _by_hand(seq):
    scores = 2 * (seq + 1) / 2 * 16 * 128 * 2
    return 3 * (28 * (PROJ + scores + MLP) + HEAD)


@pytest.mark.parametrize("cell,seq,useful", [
    # 6 selected workers x 1 row x 1024 tokens
    ("qwen3-0.6b.sim1.seq1024x1", 1024, 6 * 1 * 1024),
    # 6 selected workers x 4 rows x 64 tokens
    ("qwen3-0.6b.spmd4.seq64x4", 64, 6 * 4 * 64),
])
def test_useful_flops_per_step(cell, seq, useful):
    s, t = _cell(cell)
    assert t.seq_len == seq
    assert t.workers * t.tokens_per_worker == useful
    per_step = flops.train_flops_per_token(s, t.seq_len) * useful
    assert per_step == pytest.approx(_by_hand(seq) * useful, rel=1e-12)
    # written out: 3,928,571,904 and 3,598,270,464 FLOP per token
    assert flops.train_flops_per_token(s, seq) == {
        1024: 3_928_571_904, 64: 3_598_270_464}[seq]


def test_backup_reduce_bytes():
    # two local workers: [2, P] f32 read, [P] f32 written
    assert flops.backup_reduce_bytes(2, 596_049_920) == 12 * 596_049_920


def test_peaks_of_v5e():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v99")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
