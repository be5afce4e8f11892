"""The table of chip peaks (``bench/peaks.json``), keyed by ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path


class UnknownDevice(KeyError):
    pass


def peak(device_kind: str, path: Path | None = None) -> dict:
    """The peaks of one chip.  A device kind that is not in the table is an
    error, never a default."""
    table = json.loads((path or Path(__file__).with_name("peaks.json")).read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
