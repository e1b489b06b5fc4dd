"""The benchmark's own table of chip peaks (`peaks.json`), keyed by the
`device_kind` JAX reports. A device without a row is an error, never a
default: a utilization is not computed from another chip's peak."""

from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def lookup(device_kind: str) -> dict:
    with open(PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} has no row in {PATH} "
                       f"(known: {sorted(table)})")
    return table[device_kind]
