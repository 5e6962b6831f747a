"""The table of peaks, keyed by JAX's ``device_kind``.  An unknown device is
an error: no default, no environment override."""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in {_PATH}; add its "
            "published peaks with their source before measuring on it")
    return dict(table[device_kind])
