"""Shared benchmark utilities: the CSV row protocol.

Every benchmark module exposes ``run() -> list[Row]``; benchmarks/run.py
prints them as ``name,us_per_call,derived`` CSV (one per paper table/figure).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float
    derived: str  # free-form "key=value;key=value" payload

    def csv(self) -> str:
        return f"{self.name},{self.us_per_call:.2f},{self.derived}"
