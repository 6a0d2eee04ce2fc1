"""The chip's peaks, from ``peaks.json``, keyed by JAX's ``device_kind``.
A device that is not in the table is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict[str, float]:
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r} in {path.name}; "
            f"known: {sorted(table)}")
    return {k: float(v) for k, v in table[device_kind].items()}
