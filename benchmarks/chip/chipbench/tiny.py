"""A cell's configuration cut to a size a CPU test run holds.

Every key but the widths, depth and vocabulary is the real cell's, so
the tests drive the same harness, trainer path and comparison. The
limits are the tiny model's own (``fixtures/tiny_limits.json``), set by
the same rule as the chip cells' from CPU readings of it.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import os

from chipbench import spec

SIZES = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
         "vocab_size": 512}
LIMITS = os.path.join(spec.BENCH_DIR, "fixtures", "tiny_limits.json")


def cell(workload: str) -> spec.Cell:
    real = spec.resolve(workload)
    config = copy.deepcopy(real.config)
    config.update(SIZES)
    traffic = dict(real.traffic, seq_len=16, steps_per_block=2)
    with open(LIMITS) as f:
        limits = json.load(f)[workload]
    return dataclasses.replace(real, name=f"tiny.{workload}", config=config,
                               traffic=traffic, limits=limits)
