"""Published per-chip peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "peaks.json")


class UnknownDevice(LookupError):
    pass


def lookup(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a chip that is not in
    the table is an error, never a default."""
    with open(PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"{PATH} (known: {sorted(table)})")
    return table[device_kind]
