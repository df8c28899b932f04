"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

A device that is not in ``peaks.json`` is an error: a share of a peak
computed against a guessed peak would be a number with no meaning.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TABLE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, table: Path = TABLE) -> Dict[str, float]:
    devices = json.loads(Path(table).read_text())["devices"]
    if device_kind not in devices:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r} in {table}; known: "
                            f"{sorted(devices)}")
    return devices[device_kind]
