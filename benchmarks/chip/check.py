"""The comparison that decides ``correct``.

It reads what the window served and holds it to the plain reference
(``reference.py``), once the window has closed:

* ``failed_requests`` — requests due in the window that were refused, timed
  out, errored, came back ``degraded`` or never came back;
* ``infeasible_answers`` — served answers that do not carry every keyword
  of their query, or that are empty where the reference finds a set;
* ``misreported_diameter_rel`` — the widest relative gap between a served
  diameter and the float64 diameter of the served set;

and, on a sample of the served requests drawn from the seed (every request
when there are fewer, and always the slowest), by the request's tier:

* exact: ``optimum_gap_rel``, the widest relative gap between the served
  diameter and the reference's smallest diameter;
* approx: ``below_optimum_rel``, how far below the smallest diameter a
  served diameter lies (a feasible set cannot);
* device: ``anchor_star_gap_rel``, the widest relative gap between the
  served set's diameter and that of the float64 anchor-star set, 0 where
  the sets are the same.

Each number has its limit in ``limits.json``; the run is correct when none
of the numbers of the cell's tiers is over it. A control replaces the
served answers with a lower-precision reference (``control.py``).
"""
from __future__ import annotations

import numpy as np

import harness
import reference

TINY = 1e-300


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), TINY) if a != b else 0.0


def carries_all(corpus, ids, query) -> bool:
    off, val = corpus.kw_offsets, corpus.kw_values
    have = set()
    for i in ids:
        have.update(val[off[i]:off[i + 1]].tolist())
    return set(query) <= have


TIER_NUMBER = {"exact": "optimum_gap_rel", "approx": "below_optimum_rel",
               "device": "anchor_star_gap_rel"}


def served_answers(records) -> list:
    """(query, tier, [(ids, diameter), ...]) of every served request."""
    out = []
    for r in records:
        if r.served:
            cands = r.response.payload["candidates"]
            out.append((r.query, r.tier,
                        [(tuple(int(i) for i in c.ids), float(c.diameter))
                         for c in cands]))
    return out


def sample_indices(records, served_idx: list[int], n: int,
                   rng: np.random.Generator) -> list[int]:
    """Positions in ``served_idx`` to hold to the reference: all of them,
    or ``n`` drawn from the seed with the slowest request among them."""
    if len(served_idx) <= n:
        return list(range(len(served_idx)))
    lat = [records[i].done - records[i].due for i in served_idx]
    slowest = int(np.argmax(lat))
    rest = [i for i in range(len(served_idx)) if i != slowest]
    pick = rng.choice(len(rest), size=n - 1, replace=False)
    return sorted([slowest] + [rest[j] for j in pick])


def compare(corpus, index: reference.InvertedIndex, tiers, answers,
            sample: list[int], attempted: int) -> dict:
    """The numbers compared, {name: value}, for ``answers`` (as
    :func:`served_answers` gives them) of ``attempted`` requests of the
    mix's ``tiers``."""
    out = {"failed_requests": attempted - len(answers),
           "infeasible_answers": 0, "misreported_diameter_rel": 0.0}
    out.update({TIER_NUMBER[t]: 0.0 for t in tiers})
    for query, _, cands in answers:
        for ids, diam in cands:
            if not carries_all(corpus, ids, query):
                out["infeasible_answers"] += 1
            out["misreported_diameter_rel"] = max(
                out["misreported_diameter_rel"],
                _rel(diam, reference.set_diameter(corpus.points, ids)))
    for j in sample:
        query, tier, cands = answers[j]
        groups = [index.group(v) for v in query]
        if tier == "device":
            ref = reference.anchor_star_top1(corpus.points, groups)
        else:
            ref = reference.exact_top1(corpus.points, groups)
        if ref is None:
            out["infeasible_answers"] += len(cands)
            continue
        if not cands:
            out["infeasible_answers"] += 1
            continue
        ids, diam = cands[0]
        if tier == "exact":
            gap = _rel(diam, ref[0])
        elif tier == "approx":
            gap = max(0.0, ref[0] - diam) / max(ref[0], TINY)
        else:
            gap = 0.0 if ids == ref[1] else _rel(diam, ref[0])
        out[TIER_NUMBER[tier]] = max(out[TIER_NUMBER[tier]], gap)
    return out


def verdict(numbers: dict) -> tuple[bool, list]:
    """(correct, [[name, value, limit], ...]) for the numbers compared."""
    lim = harness.load_json(harness.HERE / "limits.json")
    rows = [[name, value, lim[name]] for name, value in numbers.items()]
    return all(v <= limit for _, v, limit in rows), rows
