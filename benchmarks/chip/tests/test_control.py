"""The control (the reference one precision down, in the program's place)
fails the check; the reference at the stated precision passes it."""
import numpy as np
import pytest

import check
import control
import harness
import reference

SMALL = dict(n=4000, u=60)


def _cfg(name, **small):
    cfg = harness.load_json(harness.HERE / "configs" / name)
    cfg.update(small or SMALL)
    return cfg


def _mix(tier):
    m = harness.load_json(harness.HERE / "mixes" / "device-serial.json")
    return dict(m, tiers=[[tier, 1]])


@pytest.mark.parametrize("tier", ["exact", "approx", "device"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_refused(tier, seed):
    m = _mix(tier)
    numbers = control.control_numbers(_cfg("paper-synth-1m.json"), m, seed,
                                      160)
    correct, rows = check.verdict(numbers)
    assert not correct, rows


@pytest.mark.parametrize("tier", ["exact", "approx", "device"])
def test_reference_at_the_stated_precision_passes(tier, monkeypatch):
    def sound(points, groups, t):
        if t == "device":
            return reference.anchor_star_top1(points, groups)
        return reference.exact_top1(points, groups)

    monkeypatch.setattr(control, "control_answer", sound)
    m = _mix(tier)
    numbers = control.control_numbers(_cfg("paper-synth-1m.json"), m, 5, 32)
    correct, rows = check.verdict(numbers)
    assert correct, rows
    assert numbers["misreported_diameter_rel"] == 0.0


def test_flickr_control_is_refused():
    m = _mix("exact")
    numbers = control.control_numbers(
        _cfg("flickr-1m.json", n=4000, u=2000), m, 4, 48)
    assert not check.verdict(numbers)[0]
    assert np.isfinite(numbers["optimum_gap_rel"])
