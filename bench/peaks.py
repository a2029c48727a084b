"""Published per-chip peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """The peaks row for ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS_FILE.name} "
                       f"(known: {sorted(table['devices'])})") from None
