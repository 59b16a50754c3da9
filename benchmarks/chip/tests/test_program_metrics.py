"""The readers of the program's own spans and counters, on a tiny
device-tier cell served on the CPU: each reads a positive number from a
served window, and nothing (None) from a program without the field."""
import dataclasses
import math

import pytest

import harness
import run
import tiny
import traffic
from harness import ARRIVALS, WINDOW, sub_rng

SEED = 2 ** 31 + 4242          # larger than 32 signed bits hold
READERS = ("queue_wait_ms_per_query", "dispatch_ms_per_query",
           "readback_ms_per_query", "rescore_ms_per_query")


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("cell")
    cell = tiny.tiny_cell(tmp, tiny.mix("device"))
    sv = run.start_serving(cell, SEED, root=tmp, require_tpu=False)
    rt, probe, mix = sv.rt, sv.probe, cell.mix
    rs0 = harness.snapshot(rt.stats)
    nb0 = len(probe.batch_stats)
    res = traffic.run_loop(rt, mix, sv.stream,
                           traffic.TierPlan(mix, sub_rng(SEED, WINDOW, 2)),
                           sub_rng(SEED, ARRIVALS), seconds=1.0,
                           span=run._span)
    rs1 = harness.snapshot(rt.stats)
    rt.close()
    return harness.Window(
        tiers=["device"], seconds=res.seconds,
        queries=sum(r.served for r in res.records),
        batch_stats=probe.batch_stats[nb0:],
        engine_seconds=sum(probe.batch_seconds[nb0:]), backend={},
        runtime=harness.counter_delta(rs0, rs1), compiles=[])


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_a_served_window(window, name):
    v = harness.reader(name)(window)
    assert v is not None and math.isfinite(v) and v > 0.0, (name, v)


def test_stages_fit_inside_the_engine_and_the_runtime(window):
    per_query = {n: harness.reader(n)(window) for n in READERS
                 + ("pack_ms_per_query", "runtime_ms_per_query")}
    engine_ms = window.per_query(1e3 * window.engine_seconds)
    stages = sum(per_query[n] for n in ("pack_ms_per_query",
                                        "dispatch_ms_per_query",
                                        "readback_ms_per_query",
                                        "rescore_ms_per_query"))
    assert stages <= engine_ms
    assert per_query["queue_wait_ms_per_query"] \
        <= per_query["runtime_ms_per_query"]


@dataclasses.dataclass
class _OlderStats:
    """A PipelineStats from before the device tier timed its readback."""

    t_pack_s: float = 0.001
    t_dispatch_s: float = 0.002
    t_rescore_s: float = 0.0


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_its_field(name):
    w = harness.Window(tiers=["device"], seconds=1.0, queries=10,
                       batch_stats=[_OlderStats(), _OlderStats()],
                       engine_seconds=0.5, backend={},
                       runtime={"batches": 10, "completed": 10}, compiles=[])
    assert harness.reader(name)(w) is None
    w.batch_stats = []
    assert harness.reader(name)(w) is None


def test_counters_reach_the_window_by_name(window):
    assert window.queries > 0
    assert window.runtime["t_queue_wait_s"] > 0.0
    assert window.runtime["t_batch_window_s"] > 0.0
