"""Published peaks per chip, keyed by JAX's ``device_kind``. A device
that is not in ``peaks.json`` is an error, never a default."""
from __future__ import annotations

import json
import os

_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(table)}); add a row to {_FILE}")
    return table[device_kind]
