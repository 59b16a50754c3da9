#!/usr/bin/env python3
"""Chip benchmark of served nearest keyword set queries: one cell, one run.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell asks
for. The cell (a workload of ``BENCHMARK.json``) names a deployment
(``configs/``) and a traffic mix (``mixes/``). One process:

1. makes the corpus from ``--seed`` and builds ``NKSEngine`` on it;
2. serves through ``ServingRuntime(engine, RuntimeConfig(backend="pallas"))``
   (the default ``PallasBackend``: cost-model routing, prune tier on auto);
3. warms up on whole queries of the cell's own mix until two successive
   chunks take the same time per query; ``setup_s`` ends here;
4. measures for ``--seconds`` with nothing else in the process: no
   profiler (unless ``--trace 1``), no logging, no reference work;
5. closes the runtime and holds what the window served to the plain
   reference (``check.py``).

With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the line carries
the per-layer metrics (``metrics/``), the device's busy time and a
breakdown. The last line on stdout is the result; the last lines on stderr
are the numbers the check compared, each beside its limit. Without a TPU,
or with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import pathlib
import shutil
import sys
import time


def _process_age_s() -> float | None:
    """Seconds since this process started (Linux), else None."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return up - int(fields[19]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


_AGE0 = _process_age_s() or 0.0
_MONO0 = time.monotonic()

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
from harness import (ARRIVALS, SAMPLE, WARMUP, WINDOW,  # noqa: E402
                     sub_rng)

WINDOW_SPAN = "chipbench.window"
SPAN_ORDER = ("chipbench.query_batch", "chipbench.client",
              "chipbench.generator", "chipbench.drain")


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def _devices(chips: int, require_tpu: bool, root: pathlib.Path):
    # libtpu writes its logs inside the checkout, not to a fixed /tmp path;
    # it reads the variable when JAX first touches the chip, just below.
    os.environ.setdefault("TPU_LOG_DIR", str(root / ".chipbench" / "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def _compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says); every program is kept, small ones too."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _memory_peak(devs) -> int | None:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Serving:
    """A cell's system under test, set up and warmed, ready to measure."""

    devs: list
    corpus: harness.Corpus
    index: reference.InvertedIndex
    probe: harness.EngineProbe
    rt: object
    stream: traffic.QueryStream       # the window's queries
    compiles: harness.CompileLog
    setup_s: float


def start_serving(cell: harness.Cell, seed: int, *,
                  root: pathlib.Path = harness.CHECKOUT,
                  require_tpu: bool = True) -> Serving:
    """Corpus, engine and runtime from ``seed``, warmed up on whole queries
    of the cell's mix. ``require_tpu`` False lets the tests run on the
    CPU."""
    devs = _devices(cell.chips, require_tpu, root)
    sys.path.insert(0, str(root / "src"))
    cache = _compile_cache(root)
    compiles = harness.CompileLog()
    compiles.install()
    from repro.serve.runtime import RuntimeConfig, ServingRuntime

    cfg, mix = cell.config, cell.mix
    corpus = harness.make_corpus(cfg, seed)
    engine = harness.build_engine(corpus, cfg, seed,
                                  [t for t, _ in mix["tiers"]])
    t_built = time.monotonic()
    index = reference.InvertedIndex(corpus.kw_offsets, corpus.kw_values,
                                    corpus.n_keywords)
    populated = index.populated()
    seen: set = set()
    warm_stream = traffic.QueryStream(populated, mix, sub_rng(seed, WARMUP),
                                      seen)
    stream = traffic.QueryStream(populated, mix, sub_rng(seed, WINDOW), seen)
    probe = harness.EngineProbe(engine, _span)
    rt = ServingRuntime(probe, RuntimeConfig(backend="pallas"))
    warm = traffic.warm_up(rt, mix, warm_stream,
                           traffic.TierPlan(mix, sub_rng(seed, WARMUP, 2)),
                           sub_rng(seed, WARMUP, 1), _span)
    setup_s = _AGE0 + time.monotonic() - _MONO0
    _say(f"setup: {setup_s:.3f} s ({time.monotonic() - t_built:.3f} s of "
         f"warm-up: {warm['queries']} queries, chunk s/query "
         f"{[round(x, 5) for x in warm['chunks']]}, steady={warm['steady']});"
         f" compiles so far {len(compiles.events)}; cache {cache}")
    return Serving(devs, corpus, index, probe, rt, stream, compiles, setup_s)


def run_cell(cell: harness.Cell, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = harness.CHECKOUT,
             require_tpu: bool = True) -> dict:
    """One run of ``cell``; returns the result object."""
    sv = start_serving(cell, seed, root=root, require_tpu=require_tpu)
    devs, rt, probe, mix = sv.devs, sv.rt, sv.probe, cell.mix

    trace_dir = root / ".chipbench" / "trace"
    if trace:
        import jax
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    be0 = harness.snapshot(rt.backend.stats)
    rs0 = harness.snapshot(rt.stats)
    nb0 = len(probe.batch_stats)
    with _span(WINDOW_SPAN):
        res = traffic.run_loop(rt, mix, sv.stream,
                               traffic.TierPlan(mix, sub_rng(seed, WINDOW, 2)),
                               sub_rng(seed, ARRIVALS), seconds=seconds,
                               span=_span)
    if trace:
        jax.profiler.stop_trace()
    be1 = harness.snapshot(rt.backend.stats)
    rs1 = harness.snapshot(rt.stats)
    batches = probe.batch_stats[nb0:]
    engine_s = sum(probe.batch_seconds[nb0:])
    memory_peak = _memory_peak(devs)
    model = getattr(rt.backend, "_model", None)
    rt.close()
    window = harness.Window(
        tiers=[t for t, _ in mix["tiers"]], seconds=res.seconds,
        queries=sum(r.served for r in res.records), batch_stats=batches,
        engine_seconds=engine_s,
        backend=harness.counter_delta(be0, be1),
        runtime=harness.counter_delta(rs0, rs1),
        compiles=sv.compiles.between(res.start, res.end),
        peaks=harness.peaks_for(devs[0].device_kind) if require_tpu
        else None)
    _report_window(res, window, model, mix)
    answers = check.served_answers(res.records)
    # The program's state goes before the reference runs.
    corpus, index, setup_s = sv.corpus, sv.index, sv.setup_s
    del rt, probe, sv
    gc.collect()

    out = {"correct": None, "attempted": len(res.records),
           "failed": len(res.records) - window.queries}
    if trace:
        tracemod = harness.load_module(HERE / "trace.py", "chipbench_trace")
        path = tracemod.find_xplane(str(trace_dir))
        devices, spans = tracemod.load(path)
        window.trace = tracemod.reduce(devices, spans, WINDOW_SPAN,
                                       SPAN_ORDER)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in cell.per_layer:
            v = harness.reader(m["name"])(window)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": window.trace.top_ops(),
                            "idle_gaps": window.trace.top_gaps()}
        _say(f"trace: busy {window.trace.busy_s:.6f} s of "
             f"{window.trace.window_s:.6f} s; top ops "
             f"{window.trace.top_ops(5)}; idle by span "
             f"{window.trace.top_gaps(5)}")
    else:
        lat = traffic.latency_ms(res)
        e2e = {"setup_s": setup_s,
               "queries_per_s": window.queries / res.seconds,
               "latency_p50_ms": traffic.percentile(lat, 50)}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    out["metrics"] = metrics
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    if trace:
        device["busy_s"] = window.trace.busy_s
        device["window_s"] = window.trace.window_s
    out["device"] = device

    t0 = time.monotonic()
    served_idx = [i for i, r in enumerate(res.records) if r.served]
    sample = check.sample_indices(res.records, served_idx,
                                  int(mix["check_sample"]),
                                  sub_rng(seed, SAMPLE))
    numbers = check.compare(corpus, index, window.tiers, answers, sample,
                            len(res.records))
    correct, rows = check.verdict(numbers)
    out["correct"] = correct
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    _say(f"check: {len(sample)} of {len(answers)} served answers held to "
         f"the reference in {time.monotonic() - t0:.3f} s")
    for n, v, lim in rows:
        _say(f"check {n}: {v!r} (limit {lim!r})")
    return out


def _report_window(res, w: harness.Window, model, mix: dict) -> None:
    late = [r.sent - r.due for r in res.records]
    be = w.backend
    _say(f"window: {len(res.records)} requests, {w.queries} served in "
         f"{res.seconds:.3f} s; completions/s by quarter "
         f"{[round(x, 3) for x in traffic.quarter_rates(res)]}")
    if mix["loop"] == "open":
        lat = traffic.latency_ms(res)
        _say(f"generator: late by {1e3 * max(late):.3f} ms at most, "
             f"{1e3 * float(np.median(late)):.3f} ms median; latency p95 "
             f"{traffic.percentile(lat, 95):.3f} ms, p99 "
             f"{traffic.percentile(lat, 99):.3f} ms")
    _say(f"routing: {be.get('dispatches', 0)} bins, "
         f"{be.get('host_routed_dispatches', 0)} routed to the host "
         f"({be.get('host_routed_subsets', 0)} of {be.get('subsets', 0)} "
         f"subsets); masked joins {be.get('join_dispatches', 0)}, prune "
         f"passes {be.get('prune_tier_dispatches', 0)}; prune tier armed: "
         f"{None if model is None else model.prune_profitable}")
    _say(f"runtime: {w.runtime.get('batches', 0)} batches of "
         f"{w.runtime.get('batched_queries', 0)} queries; compiles in "
         f"window {len(w.compiles)} "
         f"({sum(e[2] for e in w.compiles):.3f} s: "
         f"{sorted({e[1] for e in w.compiles})[:6]})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        _say(f"no result: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
