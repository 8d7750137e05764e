"""The peaks table, keyed by ``device_kind``; an unknown device is an
error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, table: Path = TABLE) -> dict:
    devices = json.loads(Path(table).read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(devices)}")
    return devices[device_kind]
