"""Published peaks of each chip, keyed by JAX's ``device_kind``
(``peaks.json``).  A device missing from the table is an error."""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


def lookup(device_kind: str, path: str = PEAKS_FILE) -> dict:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"the table has {sorted(table)}")
    return table[device_kind]
