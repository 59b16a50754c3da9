"""Cells, configurations, mixes and metric readers, found by name.

Everything that belongs to one deployment, one traffic mix or one
per-layer metric lives in a file of its own under this directory:

* ``configs/<config>.json`` — a deployment (the file ``BENCHMARK.json``
  names), with the generator that makes its corpus
  (``generators/<generator>.py``);
* ``mixes/<traffic>.json`` — a traffic mix, read by ``traffic.py``;
* ``metrics/<metric>.py`` — a per-layer reader, ``read(window) -> float |
  None``.

Adding a cell, a deployment or a reader is adding files and entries; no
code here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict          # the configuration file's contents
    mix: dict             # the mix file's contents
    end_to_end: list      # metric entries the cell reports with --trace 0
    per_layer: list       # metric entries the cell reports with --trace 1


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, bench: dict | None = None,
            root: pathlib.Path = CHECKOUT,
            mixes: pathlib.Path = HERE / "mixes") -> Cell:
    """The cell named ``workload``, its config and mix files loaded: the
    config from the file ``BENCHMARK.json`` names (relative to ``root``),
    the mix from ``<mixes>/<traffic>.json``."""
    if bench is None:
        bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    moved = {m["name"] for m in e2e}
    # A per-layer metric without a ``workloads`` key is reported wherever
    # the end-to-end metric it moves is.
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return Cell(name=workload, chips=int(w["chips"]),
                config=load_json(root / cfg_entry["file"]),
                mix=load_json(mixes / f"{w['traffic']}.json"),
                end_to_end=e2e, per_layer=layer)


def reader(metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    mod = load_module(HERE / "metrics" / f"{metric}.py",
                      "chipbench_metric_" + metric.replace(".", "_"))
    return mod.read


def generator(name: str):
    """The ``generate`` function of ``generators/<name>.py``."""
    return load_module(HERE / "generators" / f"{name}.py",
                       "chipbench_gen_" + name).generate


def sub_rng(seed: int, *tags: int) -> np.random.Generator:
    """A random stream of its own for each use of one ``--seed``."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), *tags])


# Streams drawn from one seed.
CORPUS, INDEX, WARMUP, WINDOW, ARRIVALS, SAMPLE = range(6)


@dataclasses.dataclass
class Corpus:
    points: np.ndarray        # (n, d) float32
    kw_offsets: np.ndarray    # (n+1,) int64, point -> keywords CSR
    kw_values: np.ndarray     # (nnz,) int32, sorted within each point
    n_keywords: int


def make_corpus(cfg: dict, seed: int) -> Corpus:
    points, offsets, values = generator(cfg["generator"])(
        cfg, sub_rng(seed, CORPUS))
    return Corpus(points, offsets, values, int(cfg["u"]))


def build_engine(corpus: Corpus, cfg: dict, seed: int, tiers):
    """The system under test, built the way its users build it, with the
    indexes that the cell's ``tiers`` serve from (the device tier needs
    none): an index no request reads would only lengthen set-up."""
    from repro.core.types import KeywordDataset
    from repro.serve.engine import NKSEngine
    from repro.utils.csr import CSR, invert_csr

    # The point -> keywords rows are already sorted and distinct, which is
    # what make_dataset's per-row Python pass would produce.
    kw = CSR(offsets=corpus.kw_offsets, values=corpus.kw_values)
    ds = KeywordDataset(points=corpus.points, kw=kw,
                        ikp=invert_csr(kw, corpus.n_keywords),
                        n_keywords=corpus.n_keywords)
    idx_seed = int(sub_rng(seed, INDEX).integers(0, 2 ** 31))
    return NKSEngine(ds, seed=idx_seed, build_exact="exact" in tiers,
                     build_approx="approx" in tiers, **cfg["index"])


class EngineProbe:
    """What the runtime is handed in place of the engine: forwards every
    attribute, and around each ``query_batch`` opens a host span and keeps
    the batch's ``PipelineStats`` and its wall time."""

    def __init__(self, engine, span):
        object.__setattr__(self, "_engine", engine)
        object.__setattr__(self, "_span", span)
        object.__setattr__(self, "batch_stats", [])
        object.__setattr__(self, "batch_seconds", [])

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def __setattr__(self, name, value):
        setattr(self._engine, name, value)

    def query_batch(self, queries, *args, **kwargs):
        t0 = time.perf_counter()
        with self._span("chipbench.query_batch"):
            out = self._engine.query_batch(queries, *args, **kwargs)
        self.batch_seconds.append(time.perf_counter() - t0)
        self.batch_stats.append(self._engine.last_batch_stats)
        return out


class CompileLog:
    """JAX compilations (or loads from the persistent cache) as they happen:
    (monotonic end time, function name, seconds)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.events: list[tuple[float, str, float]] = []

    def install(self) -> None:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.events.append((time.monotonic(), str(kwargs.get(
                "fun_name", "?")), float(duration)))

    def between(self, t0: float, t1: float) -> list[tuple[float, str, float]]:
        return [e for e in self.events if t0 <= e[0] <= t1]


@dataclasses.dataclass
class Window:
    """What one measured window did, for the per-layer readers."""

    tiers: list                     # the mix's tiers
    seconds: float                  # host clock, start to last completion
    queries: int                    # requests served in the window
    batch_stats: list               # PipelineStats of every window batch
    engine_seconds: float           # wall time inside query_batch
    backend: dict                   # BackendStats deltas over the window
    runtime: dict                   # RuntimeStats deltas over the window
    compiles: list                  # CompileLog events inside the window
    trace: object = None            # trace.TraceSummary of a traced run
    peaks: dict | None = None       # the device's row of peaks.json

    def per_query(self, total: float) -> float | None:
        return total / self.queries if self.queries else None


def counter_delta(before, after) -> dict:
    """Numeric fields of two snapshots of a stats dataclass, subtracted."""
    out = {}
    for f in dataclasses.fields(after):
        a, b = getattr(after, f.name), getattr(before, f.name)
        if isinstance(a, (int, float)) and not isinstance(a, bool):
            out[f.name] = a - b
    return out


def snapshot(stats):
    return dataclasses.replace(stats)


def peaks_for(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]
