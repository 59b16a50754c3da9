#!/usr/bin/env python3
"""The control of the correctness check: the reference, computed one
precision below what the configuration states, put in the program's place.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 3 \
        [--queries 64]

For each seed it makes the cell's corpus and the window's query stream as
``run.py`` does, answers the queries with the control and holds the answers
to ``check.py``'s comparison. A sound check refuses them on every seed.

* exact and approx tiers (float64 stated): the exact answer computed in
  float32, its diameter reported in float32;
* device tier (float32 selection, float64 diameters stated): the
  anchor-star set chosen from coordinates rounded to bfloat16, its
  diameter reported in float32.

The benchmark's own runs never run this. It needs no chip: it is host
arithmetic, run at the cell's own size.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
from harness import SAMPLE, WINDOW, sub_rng  # noqa: E402


def control_answer(points, groups, tier: str):
    if tier == "device":
        ans = reference.anchor_star_top1(points, groups,
                                         select_dtype=ml_dtypes.bfloat16)
        return None if ans is None else (
            reference.set_diameter(points, ans[1], np.float32), ans[1])
    return reference.exact_top1(points, groups, dtype=np.float32)


def control_numbers(cfg: dict, mix: dict, seed: int, n_queries: int) -> dict:
    """The numbers the check compares, with the control's answers."""
    corpus = harness.make_corpus(cfg, seed)
    index = reference.InvertedIndex(corpus.kw_offsets, corpus.kw_values,
                                    corpus.n_keywords)
    stream = traffic.QueryStream(index.populated(), mix,
                                 sub_rng(seed, WINDOW), set())
    tiers = traffic.TierPlan(mix, sub_rng(seed, WINDOW, 2))
    answers = []
    for _ in range(n_queries):
        q, tier = next(stream), next(tiers)
        ans = control_answer(corpus.points, [index.group(v) for v in q], tier)
        answers.append((q, tier, [] if ans is None else [(ans[1], ans[0])]))
    n = min(int(mix["check_sample"]), len(answers))
    sample = sorted(sub_rng(seed, SAMPLE).choice(len(answers), size=n,
                                                 replace=False).tolist())
    return check.compare(corpus, index, [t for t, _ in mix["tiers"]],
                         answers, sample, len(answers))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--queries", type=int, default=64)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    refused = 0
    for seed in args.seeds:
        t0 = time.monotonic()
        numbers = control_numbers(cell.config, cell.mix, seed, args.queries)
        correct, rows = check.verdict(numbers)
        refused += not correct
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control_correct": correct,
                          "seconds": time.monotonic() - t0,
                          "checks": {n: [v, lim] for n, v, lim in rows}}),
              flush=True)
    print(json.dumps({"seeds": len(args.seeds), "refused": refused}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
