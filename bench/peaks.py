"""The peak table, ``peaks.json``, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind, path=PATH):
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path} (have {sorted(table)})")
    return table[device_kind]
