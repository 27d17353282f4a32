"""The plain reference the benchmark holds the served answers to.

A table of the live key-value pairs, indexed by the key itself (the
semantics of ``chip_smoke.host_answers``' ``dict``, held as a NumPy
direct-address table so that a window's hundred million lookups check in
seconds), with writes applied in submission order and the last write of a
key winning.  It imports nothing of the program.

``control`` puts a deliberately broken reference in the program's place,
to show that the comparison fails it: ``int16_keys`` holds and compares
keys at 16 bits (their high half), the precision below the
configuration's int32 keys, so a lookup answers with the first live
record that shares its key's high half.  It breaks exact answers.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

SENTINEL_VALUE = -1
CONTROLS = ("int16_keys",)


class Reference:
    """A direct-address table over the key range: ``value[key]`` and
    ``present[key]``.  A write outside the range grows it."""

    def __init__(self, keys, values, control: Optional[str] = None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
        keys = np.asarray(keys, np.int64)
        self.lo = int(keys.min())
        size = int(keys.max()) - self.lo + 1
        self.value = np.zeros(size, np.int64)
        self.present = np.zeros(size, bool)
        self.value[keys - self.lo] = values
        self.present[keys - self.lo] = True
        self.control = control
        self._by_high_half = None  # the int16_keys control's view

    def live(self) -> int:
        return int(self.present.sum())

    def _slots(self, q: np.ndarray):
        i = q - self.lo
        inside = (i >= 0) & (i < self.present.size)
        return np.where(inside, i, 0), inside

    def lookup(self, q) -> Tuple[np.ndarray, np.ndarray]:
        """(values, found) for each key of ``q``."""
        q = np.asarray(q, np.int64)
        if self.control == "int16_keys":
            q = self._first_sharing_high_half(q)
        i, inside = self._slots(q)
        found = inside & self.present[i]
        return np.where(found, self.value[i], SENTINEL_VALUE), found

    def _first_sharing_high_half(self, q: np.ndarray) -> np.ndarray:
        """The smallest live key with the high 16 bits of each of ``q``
        (``lo - 1``, which is absent, where there is none)."""
        if self._by_high_half is None:
            live = np.flatnonzero(self.present) + self.lo
            halves, first = np.unique(live >> 16, return_index=True)
            self._by_high_half = (halves, live[first])
        halves, firsts = self._by_high_half
        pos = np.minimum(np.searchsorted(halves, q >> 16), halves.size - 1)
        return np.where(halves[pos] == q >> 16, firsts[pos], self.lo - 1)

    def upsert(self, keys, values) -> None:
        """Apply one write request: its pairs in order, the last one of a
        key winning."""
        k = np.asarray(keys, np.int64)
        v = np.asarray(values, np.int64)
        # the last occurrence of each key, found on the reversed request
        uk, first_rev = np.unique(k[::-1], return_index=True)
        uv = v[::-1][first_rev]
        below = max(0, self.lo - int(uk[0]))
        above = max(0, int(uk[-1]) - self.lo - self.present.size + 1)
        if below or above:
            self.value = np.pad(self.value, (below, above))
            self.present = np.pad(self.present, (below, above))
            self.lo -= below
        self.value[uk - self.lo] = uv
        self.present[uk - self.lo] = True
        self._by_high_half = None


class ReferenceServer:
    """A reference in the program's place: the request API the window
    drives (``submit``, ``submit_write``, ``drain``), answered by
    ``Reference``.  With a ``control`` it is the control run."""

    @dataclasses.dataclass
    class Stats:
        busy_s: float = 0.0
        chunks: int = 0
        lanes: int = 0
        updates: int = 0
        compactions: int = 0

    def __init__(self, keys, values, control: Optional[str] = None):
        self.ref = Reference(keys, values, control)
        self.stats = self.Stats()
        self._queue = []

    def submit(self, keys) -> int:
        self._queue.append(("lookup", np.asarray(keys), None))
        return len(self._queue) - 1

    def submit_write(self, keys, values) -> int:
        self._queue.append(("update", np.asarray(keys), np.asarray(values)))
        return len(self._queue) - 1

    def drain(self) -> dict:
        out = {}
        for ticket, (kind, keys, values) in enumerate(self._queue):
            if kind == "update":
                self.ref.upsert(keys, values)
                # the reference has no buffer: each write is "compacted" at once
                self.stats.updates += keys.size
                self.stats.compactions += 1
                out[ticket] = (np.asarray(keys.size),)
            else:
                out[ticket] = self.ref.lookup(keys)
        self._queue = []
        return out

    def reset_stats(self) -> None:
        self.stats = self.Stats()
