"""The least time a search of the index can take on a chip, from its work.

The work is counted from the request, whatever implements it: each lane
(one key asked for) brings its query in (4 bytes), takes its answer out
(a value and a found flag, 8 bytes), and reads one node, an 8-byte key and
value pair, on each of the tree's ``H + 1`` levels; it makes one
compare-select per level.  Bytes are priced at the chip's HBM bandwidth.
No integer vector-unit peak of the chip is published, so operations are
priced at its published int8 peak, the highest integer rate it has: that
over-states the rate and so under-states the time, and the least time
stays a lower bound.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
QUERY_BYTES = 4
ANSWER_BYTES = 8
NODE_BYTES = 8


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def search_work(lanes: int, height: int) -> tuple:
    """(bytes, ops) of ``lanes`` keys searched in a tree of ``height``."""
    levels = height + 1
    nbytes = lanes * (QUERY_BYTES + ANSWER_BYTES + levels * NODE_BYTES)
    return nbytes, lanes * levels


def least_time(lanes: int, height: int, device_kind: str) -> tuple:
    """(seconds, bound): the larger of bytes over bandwidth and operations
    over the op peak, and which of the two it is."""
    p = peaks(device_kind)
    nbytes, ops = search_work(lanes, height)
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    t_ops = ops / p["int8_ops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
