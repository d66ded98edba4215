"""Published chip peaks, keyed by the ``device_kind`` JAX reports.

The table is ``peaks.json`` beside this file, with its source. A device that
is not in the table is an error, never a default.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


def peak(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of ``device_kind``; KeyError when unknown."""
    with open(PEAKS_FILE) as f:
        kinds = json.load(f)["kinds"]
    if device_kind not in kinds:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(kinds)}")
    return dict(kinds[device_kind])
