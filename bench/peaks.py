"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

The table is ``bench/peaks.json``, each row with its source.  A kind that
is not in it is an error, never a default.
"""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
