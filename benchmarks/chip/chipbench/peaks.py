"""Published peaks of one chip, keyed by JAX's ``device_kind``.

The table is ``peaks.json`` beside this package, with its source. A
device that is not in it is an error, never a default: a share of a
peak read against the wrong chip's peak is a wrong number.
"""
from __future__ import annotations

import json
import os
from typing import Dict

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> Dict[str, float]:
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {os.path.basename(path)} (have {sorted(table)})")
    return {k: float(v) for k, v in table[device_kind].items()}
