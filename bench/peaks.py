"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports
(``bench/peaks.json``).  A kind that the table lacks is an error."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str, table: Path = TABLE) -> dict:
    peaks = json.loads(table.read_text())
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{table.name}; known: {sorted(peaks)}")
    return peaks[device_kind]
