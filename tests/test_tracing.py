"""Spans and counters of the served path.

``repro.utils.timing.span`` times a stage into a stats field and writes the
same stage into the profiler's trace. These tests hold it to that: the
seconds land in the named fields, the numpy control plane still imports
without JAX, the device tier's four stages are all timed and fit inside
the batch, the runtime counts a request's queue wait, a profiler trace
of one request through the runtime nests the spans as the code does, and
the exact tier's plan, backend and enumeration stages land in the trace
inside their batch.
"""
import dataclasses
import glob
import os
import subprocess
import sys
import textwrap
import time

import pytest

from repro.data.synthetic import random_queries, synthetic_dataset
from repro.serve.engine import NKSEngine
from repro.serve.runtime import RuntimeConfig, ServingRuntime
from repro.utils.timing import span

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


@dataclasses.dataclass
class _Stats:
    t_outer_s: float = 0.0
    t_inner_s: float = 0.0
    t_also_s: float = 0.0


def test_span_adds_seconds_to_its_fields_and_nests():
    st = _Stats()
    with span("nks.test.outer", st, "t_outer_s", n=1):
        time.sleep(0.01)
        with span("nks.test.inner", st, ("t_inner_s", "t_also_s")):
            time.sleep(0.02)
    assert st.t_inner_s >= 0.02 and st.t_also_s == st.t_inner_s
    assert st.t_outer_s >= st.t_inner_s + 0.01
    with span("nks.test.inner", st, "t_inner_s"):
        pass
    assert st.t_inner_s >= st.t_also_s        # adds, never overwrites
    with span("nks.test.no_stats"):            # stats=None times nothing
        pass


def test_span_and_engine_import_without_jax():
    code = textwrap.dedent("""
        import sys
        from repro.utils.timing import span
        import repro.serve.engine, repro.serve.runtime
        class S:
            t_x_s = 0.0
        s = S()
        with span("nks.test", s, "t_x_s", k=1):
            pass
        assert s.t_x_s > 0.0
        assert "jax" not in sys.modules, "JAX imported"
        print("ok")
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


@pytest.fixture(scope="module")
def device_engine():
    ds = synthetic_dataset(n=300, d=5, u=24, t=2, seed=0)
    eng = NKSEngine(ds, seed=3, build_exact=False, build_approx=False)
    queries = random_queries(ds, 3, 6, seed=1)
    eng.query_batch(queries[:1], k=2, tier="device")      # compile once
    return eng, queries


def test_device_tier_times_each_stage(device_engine):
    eng, queries = device_engine
    t0 = time.perf_counter()
    out = eng.query_batch(queries, k=2, tier="device")
    wall = time.perf_counter() - t0
    assert all(r.candidates for r in out)
    st = eng.last_batch_stats
    stages = [st.t_pack_s, st.t_dispatch_s, st.t_readback_s, st.t_rescore_s]
    assert all(v > 0.0 for v in stages), stages
    assert sum(stages) <= wall
    ph = st.phases
    assert ph["readback_s"] == round(st.t_readback_s, 6)
    assert ph["rescore_s"] == round(st.t_rescore_s, 6)


def test_lone_request_counts_its_queue_wait(device_engine):
    eng, queries = device_engine
    cfg = RuntimeConfig(batch_window_s=0.05)
    with ServingRuntime(eng, cfg) as rt:
        res = rt.submit({"op": "query", "keywords": queries[0], "k": 1,
                         "tier": "device"}).result(30)
        assert res.ok
        assert rt.stats.t_queue_wait_s >= cfg.batch_window_s
        assert rt.stats.t_batch_window_s > 0.0
        assert rt.health()["stats"]["t_queue_wait_s"] == \
            rt.stats.t_queue_wait_s


def _host_spans(log_dir: str) -> dict:
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    spans: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("nks."):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats)))
    return spans


def test_profiler_trace_nests_the_served_path(device_engine, tmp_path):
    import jax
    eng, queries = device_engine
    with ServingRuntime(eng, RuntimeConfig()) as rt:
        jax.profiler.start_trace(str(tmp_path))
        try:
            res = rt.submit({"op": "query", "keywords": queries[1], "k": 1,
                             "tier": "device"}).result(30)
        finally:
            jax.profiler.stop_trace()
    assert res.ok
    spans = _host_spans(str(tmp_path))
    [batch] = spans["nks.runtime.batch"]
    [qb] = spans["nks.engine.query_batch"]
    [disp] = spans["nks.device.dispatch"]
    [back] = spans["nks.device.readback"]
    [resc] = spans["nks.engine.rescore"]
    assert "nks.runtime.batch_window" in spans and "nks.device.pack" in spans
    assert batch[0] <= qb[0] and qb[1] <= batch[1]
    assert qb[0] <= disp[0] and disp[1] <= qb[1]
    assert disp[1] <= back[0] <= back[1] <= resc[0] <= resc[1] <= qb[1]
    assert batch[2]["size"] == 1
    assert qb[2]["tier"] == "device" and qb[2]["queries"] == 1
    r = eng.dataset.points_with(queries[1][0])
    assert disp[2]["q"] == 3 and disp[2]["k"] == 1 and disp[2]["r"] >= len(r)


def _backend(name):
    """The exact tier's stages on each route: the numpy loop, the Pallas
    backend's host route (its choice on the CPU), and its device route with
    the prune pass off and on."""
    from repro.core.backend import PallasBackend
    if name == "numpy":
        return "numpy"
    if name == "host":
        return PallasBackend()
    return PallasBackend(route="device",
                         prune_tier="on" if name == "prune" else "off")


@pytest.mark.parametrize("name,spans_expected", [
    ("numpy", ("nks.backend.dispatch",)),
    ("host", ("nks.backend.host",)),
    ("device", ("nks.backend.pack", "nks.backend.dispatch")),
    ("prune", ("nks.backend.pack", "nks.backend.prune")),
])
def test_exact_tier_stages_land_in_the_trace(tmp_path, name, spans_expected):
    import jax
    ds = synthetic_dataset(n=300, d=5, u=24, t=2, seed=0)
    eng = NKSEngine(ds, seed=3, build_approx=False)
    queries = random_queries(ds, 2, 4, seed=2)
    backend = _backend(name)
    eng.query_batch(queries, k=2, tier="exact", backend=backend)  # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.query_batch(queries, k=2, tier="exact", backend=backend)
    finally:
        jax.profiler.stop_trace()
    st = eng.last_batch_stats
    assert st.t_plan_s > 0.0 and st.t_enumerate_s > 0.0
    assert st.t_dispatch_s > 0.0
    spans = _host_spans(str(tmp_path))
    [qb] = spans["nks.engine.query_batch"]
    assert qb[2]["tier"] == "exact"
    for s in ("nks.engine.plan", "nks.engine.enumerate") + spans_expected:
        assert s in spans, (s, sorted(spans))
        assert all(qb[0] <= a and b <= qb[1] for a, b, _ in spans[s]), s
