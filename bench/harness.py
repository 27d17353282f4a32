"""One run of one cell: load, warm up, drive the window, check, measure.

A cell is ``<config>.<mix>``: ``bench/configs/<config>.json`` holds the
deployment and ``bench/mixes/<mix>.json`` the traffic.  Each metric is
computed by its reader, ``bench/metrics/<name>.py`` or, for a metric
``<base>.<suffix>``, ``bench/metrics/<base>.py``.  ``BENCHMARK.json`` says
which metrics a cell reports.  None of these need an edit to add a cell,
a mix or a metric.

The server is built as ``launch/serve.py --bst`` builds it: a
``BSTServer`` on the kernel descent path, sharded over a serving mesh of
the configuration's ``chips`` where that is more than one.  The window
drives it through ``submit`` / ``submit_write`` and ``drain``, and every
answer is then compared with ``reference.Reference`` replayed in
submission order.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List

import numpy as np

from bench import reference as reference_lib
from bench import trace as trace_lib
from bench import traffic as traffic_lib

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
WINDOW_STREAM, WARMUP_STREAM = 1, 2
UPDATE = traffic_lib.KINDS.index("update")


# ----------------------------------------------------------------- the cell
def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def split_cell(name: str):
    config, sep, mix = name.partition(".")
    if not sep or not config or not mix:
        raise ValueError(f"cell name {name!r} is not <config>.<mix>")
    return config, mix


def load_json(kind: str, name: str, bench_dir: Path = BENCH) -> dict:
    """``bench/<kind>/<name>.json``: a configuration or a mix."""
    return json.loads((bench_dir / kind / f"{name}.json").read_text())


def metric_specs(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics ``cell`` reports: end-to-end ones untraced, per-layer
    ones traced.  A metric without ``workloads`` belongs to every cell that
    reports the end-to-end metric it moves (or, end to end, to every cell)."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [
        m for m in bench["per_layer"]
        if cell in m.get("workloads", [cell] if m["moves"] in names else [])
    ]


def reader(name: str, bench_dir: Path = BENCH) -> Callable:
    """The ``read(run)`` function of metric ``name``."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.exists():
        path = bench_dir / "metrics" / f"{name.partition('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def tree_height(n_records: int) -> int:
    """Height of the perfect tree that holds ``n_records``."""
    return max(0, math.ceil(math.log2(n_records + 1)) - 1)


# ------------------------------------------------------------------ the run
@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers read it."""

    config: dict
    mix: dict
    seconds: float
    device_kind: str = ""
    setup_s: float = 0.0
    window_s: float = 0.0  # host clock, first submit to last answer
    keys_answered: int = 0
    submit_s: float = 0.0  # host clock around the submits, summed
    drain_s: float = 0.0  # host clock around drain(), summed
    busy_s: float = 0.0  # ServerStats.busy_s over the window
    engine_calls: int = 0  # ServerStats.chunks over the window
    lanes: int = 0  # ServerStats.lanes over the window: keys the engine searched
    compactions: int = 0
    memory_peak_bytes: int = 0
    live_records: int = 0
    trace: dict | None = None  # trace_lib.reduce of the traced window

    @property
    def height(self) -> int:
        return tree_height(self.config["records"])


@dataclasses.dataclass
class Checked:
    attempted: int
    failed: int
    checks: Dict[str, dict]

    @property
    def correct(self) -> bool:
        return all(c["value"] <= c["limit"] for c in self.checks.values())


class Answers:
    """The answers of the window, in submission order, one array per drain:
    the reads' values and found flags, key by key, and each write's
    acknowledged count.  A window's millions of answers so cost a few
    arrays to keep and nothing for Python's collector to walk."""

    def __init__(self):
        self.values: List[np.ndarray] = []
        self.found: List[np.ndarray] = []
        self.acked: List[int] = []
        self.missing: List[int] = []  # requests sent that drain() left out
        self.sent = 0

    def add(self, out: dict, tickets: List[int]) -> None:
        results = [out.get(t) for t in tickets]
        reads = [r for r in results if r is not None and len(r) == 2]
        if reads:
            self.values.append(np.concatenate([r[0] for r in reads]))
            self.found.append(np.concatenate([r[1] for r in reads]))
        self.acked.extend(int(r[0]) for r in results if r is not None and len(r) == 1)
        self.missing.extend(self.sent + i for i, r in enumerate(results) if r is None)
        self.sent += len(results)

    def answered(self) -> np.ndarray:
        """Positions, in submission order, of the requests answered."""
        return np.delete(np.arange(self.sent), self.missing)


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def build_server(config: dict, devices):
    """Load the records into a ``BSTServer`` on the kernel path, warmed for
    lookups (so that a compaction re-warms them, as a deployment's would).
    A configuration of more than one chip is served sharded over a mesh of
    exactly that many ``devices``."""
    from repro.core import distributed
    from repro.core.engine import EngineConfig
    from repro.serving import BSTServer

    if len(devices) != config["chips"]:
        raise ValueError(f"the configuration takes {config['chips']} chips, got {len(devices)}")
    mesh = None
    if config["chips"] > 1:
        mesh = distributed.make_serving_mesh(config["strategy"], devices)
    n = config["records"]
    cfg = EngineConfig(
        strategy=config["strategy"],
        n_trees=config["n_trees"],
        mapping=config["mapping"],
        use_kernel=True,
        delta_capacity=config["delta_capacity"],
    )
    srv = BSTServer(
        traffic_lib.record_keys(n), traffic_lib.record_values(n), cfg,
        chunk_size=config["chunk_size"], scan_k=config["scan_k"], mesh=mesh,
    )
    srv.warmup(("lookup",))
    return srv


@dataclasses.dataclass
class Feed:
    """A pool of requests as the loop reads it: plain Python lists of the
    offsets and kinds, made in set-up, not in the window."""

    keys: np.ndarray
    values: np.ndarray
    offsets: List[int]
    update: List[bool]

    @classmethod
    def of(cls, reqs: traffic_lib.Requests) -> "Feed":
        return cls(reqs.keys, reqs.values, reqs.offsets.tolist(), (reqs.kind == UPDATE).tolist())


def drive(srv, feed: Feed, clients: int, run: Run, answers: Answers,
          seconds: float = math.inf, drains: int | None = None) -> None:
    """The closed loop: ``clients`` requests outstanding.  Each drain answers
    every client, and each client then sends its next request, the next of
    the pool.  Runs for ``seconds`` or ``drains`` drains."""
    keys, values, offsets, update = feed.keys, feed.values, feed.offsets, feed.update
    n = len(update)
    clock = time.perf_counter
    done = 0
    t0 = clock()
    while clock() - t0 < seconds and (drains is None or done < drains):
        t = clock()
        with annotate("submit"):
            tickets = []
            for j in range(answers.sent, answers.sent + clients):
                p = j % n
                a, b = offsets[p], offsets[p + 1]
                if update[p]:
                    tickets.append(srv.submit_write(keys[a:b], values[a:b]))
                else:
                    tickets.append(srv.submit(keys[a:b]))
        t1 = clock()
        with annotate("drain"):
            out = srv.drain()
        run.submit_s += t1 - t
        run.drain_s += clock() - t1
        answers.add(out, tickets)
        done += 1


def warm_up(srv, config: dict, mix: dict, seed: int, zipf) -> None:
    """Run every program of the window before it opens.

    The mix's ``warmup`` drains of its own traffic, from another stream of
    the seed, warm the reads.  A mix with updates then writes loaded values
    back: one request as large as the buffer's high-water mark, which
    compacts it into a snapshot equal to the loaded one (its programs come
    from the persistent cache), and one single-key write, which compiles
    ingest for that snapshot.  So every run opens its window with one
    buffered write.
    """
    n = config["records"]
    clients, drains = mix["clients"], mix["warmup"]["drains"]
    reads = dict(mix, ops={"lookup": 1.0})
    reqs = traffic_lib.make_requests(reads, n, clients * drains, seed, WARMUP_STREAM, zipf)
    drive(srv, Feed.of(reqs), clients, Run(config, mix, 0.0), Answers(), drains=drains)
    if mix["ops"].get("update", 0.0) > 0.0:
        cap = config["delta_capacity"]
        keys = np.resize(reqs.keys, config.get("delta_high_water", 3 * cap // 4))
        for k in (keys, keys[:1]):
            srv.submit_write(k, ((k.astype(np.int64) - 2) // 2).astype(np.int32))
            srv.drain()
        if srv.stats.compactions < 1:
            raise RuntimeError("warm-up writes did not compact the write buffer")


def check(config: dict, reqs: traffic_lib.Requests, answers: Answers):
    """Replay the answered requests, in submission order, against the
    reference and compare every answer.  A request that drain() left out
    fails.  Returns the ``Checked`` comparison and the replayed reference."""
    n = config["records"]
    ref = reference_lib.Reference(traffic_lib.record_keys(n), traffic_lib.record_values(n))
    order = answers.answered() % reqs.n
    sizes = reqs.sizes()
    values = np.concatenate(answers.values) if answers.values else np.zeros(0, np.int32)
    found = np.concatenate(answers.found) if answers.found else np.zeros(0, bool)
    reads = reqs.kind[order] != UPDATE
    mismatched = failed = 0
    if values.size != found.size or values.size != int(sizes[order[reads]].sum()):
        # answers of the wrong length: no key can be matched to its answer
        mismatched, failed = int(sizes[order].sum()), order.size
        values = found = None
    pos = 0
    writes = iter(answers.acked)
    start = 0
    for stop in [*np.flatnonzero(~reads).tolist(), order.size]:
        js = order[start:stop]
        if js.size and values is not None:
            want_v, want_f = ref.lookup(reqs.keys_of(js))
            got_v = values[pos : pos + want_v.size].astype(np.int64)
            got_f = found[pos : pos + want_v.size].astype(bool)
            pos += want_v.size
            bad = (got_v != want_v) | (got_f != want_f)
            mismatched += int(bad.sum())
            failed += int(_per_request(bad, np.cumsum(sizes[js])).sum())
        if stop < order.size:
            sl = reqs.span(order[stop])
            ref.upsert(reqs.keys[sl], reqs.values[sl])
            if values is not None and next(writes, -1) != sl.stop - sl.start:
                mismatched += sl.stop - sl.start
                failed += 1
        start = stop + 1
    unanswered = len(answers.missing)
    return Checked(
        attempted=answers.sent,
        failed=failed + unanswered,
        checks={
            "mismatched_keys": {"value": mismatched, "limit": 0},
            "unanswered_requests": {"value": unanswered, "limit": 0},
        },
    ), ref


def _per_request(bad: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Per request, whether any of its keys was answered wrongly."""
    starts = np.concatenate([[0], ends[:-1]])
    return np.add.reduceat(bad.astype(np.int64), starts) > 0 if bad.size else np.zeros(0, bool)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def execute(config: dict, mix: dict, seed: int, seconds: float, trace: bool,
            t_start: float, devices, make_server: Callable = build_server):
    """Everything of a run after the look for a chip.  ``make_server``
    builds what the window drives from the configuration and the devices
    (the control passes the reference).  Returns the ``Run`` and the
    ``Checked`` comparison."""
    run = Run(config=config, mix=mix, seconds=seconds, device_kind=devices[0].device_kind)
    n = config["records"]
    with annotate("generate"):
        zipf = traffic_lib.Zipfian(n, mix["keys"]["theta"])
        reqs = traffic_lib.make_requests(mix, n, mix["pool_requests"], seed, WINDOW_STREAM, zipf)
        feed = Feed.of(reqs)
    srv = make_server(config, devices)
    warm_up(srv, config, mix, seed, zipf)
    srv.reset_stats()
    # what set-up left behind is long-lived: keep the collector off it
    gc.collect()
    gc.freeze()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    run.setup_s = time.perf_counter() - t_start
    answers = Answers()
    try:
        if trace:
            trace_lib.start(trace_dir)
        t0 = time.perf_counter()
        with annotate("window"):
            drive(srv, feed, mix["clients"], run, answers, seconds=seconds)
        run.window_s = time.perf_counter() - t0
        if trace:
            trace_lib.stop()
        s = srv.stats
        run.busy_s, run.engine_calls, run.lanes = s.busy_s, s.chunks, s.lanes
        run.compactions = s.compactions
        run.keys_answered = int(reqs.sizes()[answers.answered() % reqs.n].sum())
        run.memory_peak_bytes = memory_peak(devices)
        del srv
        with annotate("check"):
            checked, ref = check(config, reqs, answers)
        run.live_records = ref.live()
        if trace:
            devs, host = trace_lib.load(trace_dir)
            run.trace = trace_lib.reduce(devs, host)
    finally:
        gc.unfreeze()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return run, checked
