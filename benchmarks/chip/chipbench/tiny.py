"""A cell's configuration cut to a size a CPU test run holds.

The architecture's ``TINY`` (``archs/<model_type>.py``) names the keys
cut: the widths, depth and vocabulary. Every other key is the real
cell's, so the tests drive the same harness, trainer path and
comparison. The limits are the tiny model's own
(``fixtures/tiny/<workload>.json``), set by the same rule as the chip
cells' from CPU readings of it.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import os

from chipbench import spec


def cell(workload: str, root: str = spec.ROOT) -> spec.Cell:
    real = spec.resolve(workload, root=root)
    config = copy.deepcopy(real.config)
    config.update(spec.arch(config["model_type"], real.bench_dir).TINY)
    traffic = dict(real.traffic, seq_len=16, steps_per_block=2)
    path = os.path.join(real.bench_dir, "fixtures", "tiny", workload + ".json")
    with open(path) as f:
        limits = json.load(f)
    return dataclasses.replace(real, name=f"tiny.{workload}", config=config,
                               traffic=traffic, limits=limits)
