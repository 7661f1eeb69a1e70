"""The table of peaks, and the counts no model family owns. What a model
needs a token and a step is its family's (`families/<family>/costs.py`,
found through the configuration's `family`)."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"benchmark/peaks.json with its source")
    return table[device_kind]


def prefill_attention_positions(reused: int, prefilled: int) -> float:
    """Sum over the prefilled positions of how many keys each attends to."""
    end = reused + prefilled
    return (end * (end + 1) - reused * (reused + 1)) / 2.0
