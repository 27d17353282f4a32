"""Traffic of a cell: the loaded records and the requests, from a mix file.

One general generator reads every mix (``bench/mixes/<mix>.json``):

* ``ops`` -- the share of requests of each kind (``lookup``, ``update``).
  A request carries keys of one kind.
* ``keys`` -- ``scrambled_zipfian`` with ``theta``: YCSB's Zipfian over the
  loaded records (Gray et al., SIGMOD 1994, as YCSB's ZipfianGenerator
  computes it), hot records spread over the key space by a permutation
  drawn from the seed.  Every key requested is a loaded record.
* ``keys_per_request`` -- the keys one request carries: 1 for YCSB, whose
  every operation names one record.
* ``loop`` -- ``closed``, with ``clients`` requests outstanding.
* ``pool_requests`` -- the distinct requests generated; the loop cycles
  through them.

The seed changes which keys are asked for, the update values and the
order of kinds -- never the amount of work: each kind takes an even,
fixed slice of the pool's positions, so every seed offers the same
requests per kind in another order.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import numpy as np

KINDS = ("lookup", "update")
GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


def record_keys(n_records: int) -> np.ndarray:
    """YCSB's load phase: record ``i`` has key ``2i + 2`` (odd keys stay free
    for inserts) and value ``i``, the row id of a record kept off the device."""
    return np.arange(2, 2 * n_records + 2, 2, dtype=np.int64).astype(np.int32)


def record_values(n_records: int) -> np.ndarray:
    return np.arange(n_records, dtype=np.int32)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent stream of one seed (any whole number, even above 2**63)."""
    return np.random.default_rng([seed % (1 << 64), stream])


class Zipfian:
    """YCSB's ZipfianGenerator over ranks ``0 .. n-1`` (rank 0 is hottest)."""

    def __init__(self, n: int, theta: float):
        if not 0.0 < theta < 1.0:
            raise ValueError("theta must lie in (0, 1)")
        self.n = n
        self.theta = theta
        self.zetan = float(np.sum(np.arange(1, n + 1, dtype=np.float64) ** -theta))
        zeta2 = 1.0 + 0.5**theta
        self.alpha = 1.0 / (1.0 - theta)
        self.eta = (1.0 - (2.0 / n) ** (1.0 - theta)) / (1.0 - zeta2 / self.zetan)

    def ranks(self, u: np.ndarray) -> np.ndarray:
        """Ranks for uniform draws ``u`` in [0, 1)."""
        uz = u * self.zetan
        r = (self.n * (self.eta * u - self.eta + 1.0) ** self.alpha).astype(np.int64)
        r = np.where(uz < 1.0 + 0.5**self.theta, 1, r)
        r = np.where(uz < 1.0, 0, r)
        return np.minimum(r, self.n - 1)

    def head_share(self, k: int) -> float:
        """Probability that a draw lands on the ``k`` hottest ranks."""
        return float(np.sum(np.arange(1, k + 1, dtype=np.float64) ** -self.theta)) / self.zetan


def kind_pattern(n: int, shares: Dict[str, float]) -> np.ndarray:
    """Kind index of each of ``n`` positions: a low-discrepancy sequence, so
    each kind takes its share of the positions, evenly spread."""
    names = [k for k in KINDS if shares.get(k, 0.0) > 0.0]
    unknown = set(shares) - set(KINDS)
    if unknown:
        raise ValueError(f"unknown op kinds {sorted(unknown)}")
    total = sum(shares[k] for k in names)
    if not math.isclose(total, 1.0, abs_tol=1e-9):
        raise ValueError(f"op shares sum to {total}, not 1")
    cum = np.cumsum([shares[k] for k in names])
    u = np.mod((np.arange(n) + 1) * GOLDEN, 1.0)
    idx = np.minimum(np.searchsorted(cum, u, side="right"), len(names) - 1)
    return np.array([KINDS.index(names[i]) for i in range(len(names))])[idx]


@dataclasses.dataclass
class Requests:
    """A pool of requests.

    Pool request ``j`` carries ``keys[offsets[j]:offsets[j+1]]`` (and
    ``values`` there for updates).  The ``i``-th request sent is pool
    request ``i % n``: a loop cycles through the pool, so that generating
    the traffic stays bounded however long the window is.
    """

    kind: np.ndarray  # (n,) index into KINDS
    offsets: np.ndarray  # (n + 1,) int64
    keys: np.ndarray  # (sum of sizes,) int32
    values: np.ndarray  # (sum of sizes,) int32, meaningful for updates

    @property
    def n(self) -> int:
        return int(self.kind.size)

    def sizes(self) -> np.ndarray:
        return np.diff(self.offsets)

    def span(self, j: int) -> slice:
        return slice(int(self.offsets[j]), int(self.offsets[j + 1]))

    def keys_of(self, js: np.ndarray) -> np.ndarray:
        """The keys of pool requests ``js``, concatenated in that order."""
        lens = self.sizes()[js]
        ends = np.cumsum(lens)
        shift = np.repeat(self.offsets[js] - (ends - lens), lens)
        return self.keys[np.arange(int(ends[-1]) if ends.size else 0) + shift]


def make_requests(
    mix: dict, n_records: int, n_pool: int, seed: int, stream: int,
    zipf: Zipfian | None = None,
) -> Requests:
    """A pool of ``n_pool`` requests of ``mix`` from ``seed``'s stream
    ``stream``.  Pass a ``zipf`` built once for ``n_records`` to skip
    recomputing its constant."""
    keys_spec = mix["keys"]
    if keys_spec["distribution"] != "scrambled_zipfian":
        raise ValueError(f"unknown key distribution {keys_spec['distribution']!r}")
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    per = int(mix["keys_per_request"])
    if per < 1:
        raise ValueError("keys_per_request must be at least 1")
    rng = rng_for(seed, stream)
    kinds = kind_pattern(n_pool, mix["ops"])[rng.permutation(n_pool)]
    offsets = np.arange(n_pool + 1, dtype=np.int64) * per

    zipf = zipf if zipf is not None else Zipfian(n_records, keys_spec["theta"])
    # the hot-set scramble: rank -> record, a permutation of the records
    scramble = rng_for(seed, 0).permutation(n_records)
    records = scramble[zipf.ranks(rng.random(n_pool * per))]
    keys = (2 * records + 2).astype(np.int32)
    values = rng.integers(0, 2**31 - 1, size=keys.size, dtype=np.int32)
    return Requests(kinds, offsets, keys, values)
