"""The table of peaks, keyed by ``device_kind``. An unknown kind is an error."""

from __future__ import annotations

import json
import pathlib

_TABLE = pathlib.Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    table = {k: v for k, v in json.loads(_TABLE.read_text()).items()
             if not k.startswith("_")}
    kind = device_kind.lower()
    for prefix in sorted(table, key=len, reverse=True):
        if kind.startswith(prefix):
            return table[prefix]
    raise KeyError(
        f"no published peaks for device kind {device_kind!r}; "
        f"known: {sorted(table)} (add a row to {_TABLE.name} with its source)"
    )
