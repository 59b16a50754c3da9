"""``chip_smoke.py`` at a tiny size on the CPU, through the XLA lowering.

The script's purpose is the chip; these tests keep its phases from rotting
and pin its refusal to report success anywhere else."""
import argparse
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_one_chip_phases_on_cpu(smoke, capsys):
    smoke.run_one_chip(argparse.Namespace(seed=0, n=4000, chips=1), 6, 2)
    out = capsys.readouterr().out
    for name in ("auto", "pinned"):
        for tier in ("exact", "approx"):
            assert f"answers[{name}] {tier}: 8/8 identical" in out
    assert "answers device: 8/8 agree with the float64 anchor-star" in out
    assert "brute force: 12/12" in out
    assert "(N cut from 1000000 by --n)" in out


def test_pinned_run_dispatches_both_kernels(smoke):
    ds = smoke.generate(3000, 1)
    engine = smoke.build(ds, 1)
    work = smoke.workload(ds, 1, 4, 1)
    _, rep = smoke.serve(engine, work, smoke.PallasBackend(
        route="device", prune_tier="on"), tiers=("exact",))
    assert rep["join_dispatches"] > 0 and rep["prune_dispatches"] > 0
    assert rep["bins_host"] == 0 and rep["h2d_bytes"] > 0


def test_same_answer_allows_only_kth_rank_ties(smoke):
    from repro.core.types import Candidate, make_dataset
    import numpy as np
    pts = np.array([[0, 0], [3, 0], [0, 3], [1, 1]], np.float32)
    ds = make_dataset(pts, [[0], [1], [1], [1]], n_keywords=2)
    a, b = Candidate((0, 1), 3.0), Candidate((0, 2), 3.0)
    near = Candidate((0, 3), float(np.sqrt(2.0)))
    assert smoke.same_answer([near, a], [near, b], ds, [0, 1])
    assert not smoke.same_answer([a, near], [b, near], ds, [0, 1])
    assert not smoke.same_answer([Candidate((0, 1), 3.5)], [a], ds, [0, 1])


def test_device_tier_check_holds_answers_to_fp32_rounding(smoke):
    """The device-tier check passes the engine's answers and refuses a set
    off by more than fp32 rounding, an unrescored diameter, and a set that
    is not the anchor-star one."""
    import dataclasses
    ds = smoke.generate(3000, 2)
    engine = smoke.build(ds, 2)
    work = smoke.workload(ds, 2, 3, 1)
    answers = {("device", i): r.candidates for i, r in enumerate(
        engine.query_batch([q for q, _ in work[:3]], k=1, tier="device")
        + engine.query_batch([q for q, _ in work[3:]], k=5, tier="device"))}
    assert smoke.check_device_tier(answers, ds, work) == len(work)

    def broken(fn):
        bad = dict(answers)
        bad[("device", 3)] = fn(answers[("device", 3)])
        with pytest.raises(smoke.SmokeFailure):
            smoke.check_device_tier(bad, ds, work)

    broken(lambda c: [dataclasses.replace(c[0], diameter=c[0].diameter
                                          * (1 + 1e-4))] + c[1:])
    broken(lambda c: [c[1], c[0]] + c[2:])      # rank order swapped
    broken(lambda c: c[:-1])                    # a set missing


def test_sharded_phase_on_four_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import argparse, chip_smoke as cs; cs.run_sharded(argparse."
            "Namespace(seed=0, n=4000, chips=4), 4, 6, 2)")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    for tier in ("exact", "approx", "device"):
        assert (f"sharded[{tier}]: 8/8 answers bit-exact against one "
                f"device") in out.stdout
    assert "per device [8, 8, 8, 8]" in out.stdout.split("sharded[device]")[1]


def test_main_fails_without_a_tpu(smoke, capsys):
    assert smoke.main(["--n", "1000"]) != 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["ok"] is False and "no TPU" in last["error"]
