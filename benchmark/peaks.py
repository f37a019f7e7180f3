"""The table of published device peaks (peaks.json), keyed by device_kind."""

import json
import os


def device_peaks(kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if kind not in table:
        raise KeyError(f"no published peak for device kind {kind!r} "
                       f"(known: {sorted(table)})")
    return table[kind]
