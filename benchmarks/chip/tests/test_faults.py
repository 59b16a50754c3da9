"""A whole run on the CPU, the chip check skipped: sound, it is correct;
with the served path broken underneath, ``correct`` comes out false.

The faults a served NKS cell can have: an answer altered where the engine
produces it, and half of a batch left out. (One chip: no exchange between
chips; no training step.)"""
import dataclasses

import pytest

import run
import tiny
from repro.serve.engine import NKSEngine

SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold


def _run(tmp_path, mix=None):
    cell = tiny.tiny_cell(tmp_path, mix or tiny.mix("exact"))
    return run.run_cell(cell, SEED, 1.5, False, root=tmp_path,
                        require_tpu=False)


@pytest.mark.parametrize("tier,loop", [("exact", "closed"),
                                       ("device", "closed"),
                                       ("approx", "open")])
def test_sound_run_is_correct(tmp_path, tier, loop):
    out = _run(tmp_path, tiny.mix(tier, loop))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


def _patch(monkeypatch, alter):
    orig = NKSEngine.query_batch

    def broken(self, queries, *a, **kw):
        return [dataclasses.replace(r, candidates=alter(i, r.candidates))
                for i, r in enumerate(orig(self, queries, *a, **kw))]

    monkeypatch.setattr(NKSEngine, "query_batch", broken)


def test_altered_answer_is_refused(tmp_path, monkeypatch):
    def swap_a_point(i, cands):
        return [dataclasses.replace(c, ids=tuple(sorted(
            {(c.ids[0] + 1) % 3000} | set(c.ids[1:])))) for c in cands]

    _patch(monkeypatch, swap_a_point)
    for tier in ("exact", "device"):
        out = _run(tmp_path, tiny.mix(tier))
        assert not out["correct"], tier


def test_half_of_the_batch_left_out_is_refused(tmp_path, monkeypatch):
    calls = {"n": 0}

    def drop_every_other(i, cands):
        calls["n"] += 1
        return [] if calls["n"] % 2 else cands

    _patch(monkeypatch, drop_every_other)
    out = _run(tmp_path, tiny.mix("approx", "open"))
    assert not out["correct"]
    assert out["checks"]["infeasible_answers"]["value"] > 0
