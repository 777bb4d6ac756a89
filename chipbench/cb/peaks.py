"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports (``chipbench/peaks.json``).  A kind that is not
in the table is an error: no device silently borrows another's peaks."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> Dict:
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r} in {path}; "
            f"known: {sorted(table)}")
    return table[device_kind]
