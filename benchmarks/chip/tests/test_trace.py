"""Reduction of a profiler trace to busy time, op time and idle by span."""
import jax
import jax.numpy as jnp
import pytest

import harness

tr = harness.load_module(harness.HERE / "trace.py", "chipbench_trace")


def ev(name, s, e):
    return tr.Event(name, s, e)


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 4), (6, 20)], 2, 10) == [(2, 4), (6, 10)]
    assert tr.length([(0, 2), (3, 5)]) == 4


def test_reduce_by_hand():
    # window [100, 200); ops busy [110,130) and [150,160), one op overlaps
    # the window's start and counts only inside it.
    ops = [ev("%join.1 = (u32[8,128,4]) custom-call(f32[8,128,32])", 110, 120),
           ev("%join.1 = (u32[8,256,8]) custom-call(f32[8,256,32])", 115, 130),
           ev("prune", 150, 160), ev("early", 90, 105)]
    modules = [ev("jit_join(123)", 108, 131), ev("jit_prune(7)", 190, 230)]
    spans = [ev("chipbench.window", 100, 200),
             ev("chipbench.query_batch", 100, 140),
             ev("chipbench.client", 100, 190)]
    s = tr.reduce([{"ops": ops, "modules": modules}], spans,
                  "chipbench.window",
                  ("chipbench.query_batch", "chipbench.client"))
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(35e-9)       # 5 + 20 + 10
    assert s.idle_pct == pytest.approx(65.0)
    assert s.op_seconds["%join.1"] == pytest.approx(25e-9)
    assert s.op_seconds["early"] == pytest.approx(5e-9)
    assert s.op_counts == {"%join.1": 2, "prune": 1, "early": 1}
    assert s.module_seconds == {"jit_join": pytest.approx(23e-9),
                                "jit_prune": pytest.approx(10e-9)}
    # idle: [105,110) [130,150) [160,200); query_batch covers up to 140
    assert s.idle_by_span["chipbench.query_batch"] == pytest.approx(15e-9)
    assert s.idle_by_span["chipbench.client"] == pytest.approx(40e-9)
    assert s.idle_by_span["no_host_span"] == pytest.approx(10e-9)
    assert s.top_ops(1) == [["%join.1", pytest.approx(25e-9)]]
    assert "f32[8,256,32]" in s.ops[1].name


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        tr.reduce([{"ops": [ev("join", 0, 1)]}], [], "chipbench.window")


def test_a_trace_recorded_on_the_cpu(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.query_batch"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("chipbench.client"):
                sum(range(20000))
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))

    # On the CPU, XLA's ops run on host threads named tf_XLA*.
    def cpu_ops(plane, line):
        return "ops" if plane == "/host:CPU" and line.startswith("tf_XLA") \
            else None

    devices, spans = tr.load(path, lines=cpu_ops)
    names = {s.name for s in spans}
    assert {"chipbench.window", "chipbench.query_batch",
            "chipbench.client"} <= names
    s = tr.reduce(devices, spans, "chipbench.window",
                  ("chipbench.query_batch", "chipbench.client"))
    assert 0 < s.busy_s < s.window_s
    assert sum(s.op_counts.values()) > 0
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert s.idle_by_span.get("chipbench.client", 0) > 0
