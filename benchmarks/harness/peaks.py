"""Published peaks of one chip, keyed by the exact ``device_kind`` JAX
reports.  A device that is not in the table is an error, not a default."""

from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(table)}); add a row with its source to "
            f"benchmarks/harness/peaks.json")
    return table[device_kind]
