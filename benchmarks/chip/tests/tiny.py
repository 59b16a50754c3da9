"""A tiny cell for CPU runs of the harness: the paper's synthetic setting
cut to a few thousand points, in a benchmark description of its own, with
the committed mix or one made from it for another tier or loop."""
import json
import pathlib

import harness


def mix(tier: str = "device", loop: str = "closed") -> dict:
    """The committed closed-loop mix, on ``tier``; an open loop at a rate
    a tiny corpus keeps up with."""
    m = harness.load_json(harness.HERE / "mixes" / "device-serial.json")
    m["tiers"] = [[tier, 1]]
    if loop == "open":
        m.update(loop="open", rate_qps=40.0,
                 warmup=dict(m["warmup"], chunk=32, tol=0.5))
    return m


def tiny_cell(tmp: pathlib.Path, m: dict | None = None, n: int = 3000,
              u: int = 60) -> harness.Cell:
    cfg = harness.load_json(harness.HERE / "configs" / "paper-synth-1m.json")
    cfg.update(n=n, u=u)
    (tmp / "configs").mkdir(exist_ok=True)
    (tmp / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (tmp / "mixes").mkdir(exist_ok=True)
    (tmp / "mixes" / "tiny-mix.json").write_text(json.dumps(m or mix()))
    bench = harness.load_json(harness.CHECKOUT / "BENCHMARK.json")
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "configs/tiny.json", "reduced": ["n", "u"],
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny-cell", "config": "tiny",
                           "traffic": "tiny-mix", "chips": 1, "why": "test"}]
    for m_ in bench["end_to_end"] + bench["per_layer"]:
        m_.pop("workloads", None)
    return harness.resolve("tiny-cell", bench=bench, root=tmp,
                           mixes=tmp / "mixes")
