#!/usr/bin/env python3
"""Chip smoke test: serve the paper's synthetic deployment on a TPU.

    python chip_smoke.py [--seed 0] [--n 1000000]      # one chip
    python chip_smoke.py --chips 4                      # sharded plane only

The deployment is the paper's §VIII synthetic setting
(``configs/promish_default.PAPER_SYNTH``): coordinates uniform in
[0, 10000], a dictionary of U=1,000 keywords, t=1 keyword per point, N
points at d=32 (the launcher's default width). One process does
everything, and every input is generated from ``--seed``.

One chip (the default):

  1. build ``NKSEngine(corpus, m=2, n_scales=5)``;
  2. serve a few dozen 3-keyword queries at k=1 and a few 5-keyword queries
     at k=5 on the exact, approx and device tiers through
     ``ServingRuntime`` twice: once with ``RuntimeConfig(backend="pallas")``
     (the default ``PallasBackend``, cost-model routing) and once with the
     backend pinned to the device with the bf16 prune tier on, so both join
     kernels run on the chip;
  3. check that the exact and approx answers are identical to
     ``backend="numpy"`` on the same engine, that the device tier agrees
     with a float64 anchor-star reference on the host to fp32 rounding
     (``anchor_star``), and that on a 2,000-point corpus the exact tier
     matches ``core.brute_force.search``.

``--chips 4`` runs only the sharded path: engines on a 4-way ``data`` mesh
against a one-device engine, exact and approx tiers on the pinned pallas
backend plus the device tier, with dispatches on every device. Answers must
be bit-exact on every tier.

The script fails (non-zero exit, last line ``{"ok": false, ...}``) when JAX
sees no TPU, when a response is not ``ok`` or is degraded, when the runtime
counts an error, a failed dispatch or a per-request fallback, when the
pinned run issued no masked-join or no prune dispatch, or when an answer
disagrees. On success the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Every time it prints is host wall-clock seconds around a phase; no device
time is measured here.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.configs.promish_default import PAPER_SYNTH  # noqa: E402
from repro.core import brute_force  # noqa: E402
from repro.core.backend import PallasBackend  # noqa: E402
from repro.core.subset_search import is_minimal_candidate  # noqa: E402
from repro.data.synthetic import random_queries, synthetic_dataset  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.serve.engine import NKSEngine  # noqa: E402
from repro.serve.runtime import RuntimeConfig, ServingRuntime  # noqa: E402

PAPER_N = 1_000_000
D = 32
N3, N5 = 36, 6        # 3-keyword queries at k=1, 5-keyword queries at k=5
TIERS = ("exact", "approx", "device")
SMALL_N, SMALL_U = 2_000, 200   # brute-force corpus: ~10 points per keyword


class SmokeFailure(RuntimeError):
    """A check of the smoke test failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ phases
def generate(n: int, seed: int, *, d: int = D, u: int = PAPER_SYNTH["u"]):
    return synthetic_dataset(n=n, d=d, u=u, t=PAPER_SYNTH["t"], seed=seed,
                             coord_range=PAPER_SYNTH["coord_range"])


def build(ds, seed: int, mesh=None) -> NKSEngine:
    return NKSEngine(ds, m=2, n_scales=5, seed=seed, mesh=mesh)


def workload(ds, seed: int, n3: int, n5: int) -> list[tuple[list[int], int]]:
    """(keywords, k) pairs: 3-keyword queries at k=1, 5-keyword at k=5."""
    return ([(q, 1) for q in random_queries(ds, 3, n3, seed=seed + 1)]
            + [(q, 5) for q in random_queries(ds, 5, n5, seed=seed + 2)])


def _by_k(work):
    groups: dict[int, list[int]] = {}
    for i, (_, k) in enumerate(work):
        groups.setdefault(k, []).append(i)
    return groups


def reference(engine: NKSEngine, work, tiers=("exact", "approx")) -> dict:
    """Answers of ``backend="numpy"`` on the same engine, {(tier, i): cands}."""
    out = {}
    for tier in tiers:
        for k, idx in _by_k(work).items():
            res = engine.query_batch([work[i][0] for i in idx], k=k,
                                     tier=tier, backend="numpy")
            out.update({(tier, i): r.candidates for i, r in zip(idx, res)})
    return out


def serve(engine: NKSEngine, work, backend, tiers=TIERS) -> tuple[dict, dict]:
    """Serve ``work`` on every tier through ``ServingRuntime``.

    The first exact query goes alone (it pays calibration and the first
    compiles); the rest are submitted together so the runtime coalesces
    them. Returns ({(tier, i): candidates}, report). Raises SmokeFailure on
    any response that is not ok or is degraded, and on any runtime error,
    failed dispatch or per-request fallback."""
    rt = ServingRuntime(engine, RuntimeConfig(backend=backend))
    answers, bad = {}, []

    def req(tier, i):
        q, k = work[i]
        return {"op": "query", "keywords": q, "k": k, "tier": tier}

    def take(tier, i, resp):
        if not resp.ok or resp.degraded:
            bad.append(f"{tier} query {i}: status={resp.status} "
                       f"degraded={resp.degraded} error={resp.error}")
        else:
            answers[(tier, i)] = resp.payload["candidates"]

    try:
        t0 = time.perf_counter()
        take(tiers[0], 0, rt.submit(req(tiers[0], 0)).result())
        t1 = time.perf_counter()
        tickets = [(tier, i, rt.submit(req(tier, i)))
                   for tier in tiers for i in range(len(work))
                   if (tier, i) != (tiers[0], 0)]
        for tier, i, t in tickets:
            take(tier, i, t.result())
        t2 = time.perf_counter()
    finally:
        rt.close()
    st = rt.stats
    _check(not bad, "responses not ok: " + "; ".join(bad[:5]))
    _check(st.errors == 0 and st.dispatch_failures == 0
           and st.single_fallbacks == 0,
           f"runtime errors={st.errors} dispatch_failures="
           f"{st.dispatch_failures} single_fallbacks={st.single_fallbacks}")
    bs = rt.backend.stats
    device_bins = bs.dispatches - bs.host_routed_dispatches
    report = {
        "first_query_s": t1 - t0,
        "steady_queries_s": t2 - t1,
        "requests": len(tickets) + 1,
        "batches": st.batches,
        "bins_device": device_bins,
        "bins_host": bs.host_routed_dispatches,
        "subsets_host": bs.host_routed_subsets,
        "subsets": bs.subsets,
        "join_dispatches": bs.join_dispatches,
        "prune_dispatches": bs.prune_tier_dispatches,
        "h2d_bytes": bs.h2d_bytes,
        "d2h_bytes": bs.d2h_bytes,
    }
    return answers, report


def same_answer(got, want, ds, query, rel_tol: float = 1e-12) -> bool:
    """Identical ids and diameters (to ``rel_tol``: float64 rounding, or the
    float32 the brute-force oracle stores), except that a diameter tie at
    the k-th rank may be settled by either of the tied candidates."""
    if len(got) != len(want):
        return False
    if any(not math.isclose(g.diameter, w.diameter, rel_tol=rel_tol)
           for g, w in zip(got, want)):
        return False
    if not want:
        return True
    kth = want[-1].diameter

    def tied(c):
        return math.isclose(c.diameter, kth, rel_tol=rel_tol)

    if [c.ids for c in got if not tied(c)] != \
            [c.ids for c in want if not tied(c)]:
        return False
    return all(is_minimal_candidate(c.ids, query, ds)
               and math.isclose(brute_force.set_diameter(c.ids, ds),
                                c.diameter, rel_tol=1e-9)
               for c in got if tied(c))


def compare(answers: dict, ref: dict, ds, work, tiers) -> dict:
    """Count answers identical to the reference; raises on a mismatch."""
    out = {}
    for tier in tiers:
        same = ties = 0
        for i, (q, _) in enumerate(work):
            got, want = answers[(tier, i)], ref[(tier, i)]
            _check(same_answer(got, want, ds, q),
                   f"{tier} query {q}: {got} != numpy {want}")
            same += 1
            ties += [c.ids for c in got] != [c.ids for c in want]
        out[tier] = (same, ties)
    return out


def anchor_star(ds, query, k: int) -> list[tuple[tuple[int, ...], float]]:
    """The device tier's semantics in float64 on the host: every point of
    ``query[0]`` anchors the set of its nearest point of each other keyword;
    the k sets of smallest diameter, as (sorted ids, diameter)."""
    groups = [ds.points_with(v) for v in query]
    anchors = groups[0]
    a = ds.points[anchors].astype(np.float64)
    members = [anchors]
    for g in groups[1:]:
        b = ds.points[g].astype(np.float64)
        sq = (a * a).sum(1)[:, None] + (b * b).sum(1)[None, :] - 2.0 * a @ b.T
        members.append(g[np.argmin(sq, axis=1)])
    sets = np.stack(members, axis=1)                          # (A, q)
    pts = ds.points[sets].astype(np.float64)                  # (A, q, d)
    diff = pts[:, :, None, :] - pts[:, None, :, :]
    diam = np.sqrt(np.einsum("aijd,aijd->aij", diff, diff).max(axis=(1, 2)))
    top = np.argsort(diam, kind="stable")[:k]
    return [(tuple(sorted(set(sets[t].tolist()))), float(diam[t]))
            for t in top]


def check_device_tier(answers: dict, ds, work) -> int:
    """Device-tier answers are the float64 anchor-star answer up to fp32
    rounding: at every rank the same set, or a diameter within 1e-5 of the
    reference's; each set covers the query, and its diameter is its exact
    float64 diameter. Returns how many queries had identical ids."""
    same_ids = 0
    for i, (q, k) in enumerate(work):
        got, want = answers[("device", i)], anchor_star(ds, q, k)
        _check(len(got) == len(want),
               f"device tier {q}: {len(got)} sets, reference {len(want)}")
        for c, (ids, diam) in zip(got, want):
            _check(is_minimal_candidate(c.ids, q, ds),
                   f"device tier candidate {c.ids} does not cover {q}")
            _check(c.diameter == brute_force.set_diameter(c.ids, ds),
                   f"device tier diameter {c.diameter} of {c.ids} is not "
                   f"its float64 diameter")
            _check(c.ids == ids or math.isclose(c.diameter, diam,
                                                rel_tol=1e-5),
                   f"device tier {q}: {c.ids} at {c.diameter} vs float64 "
                   f"anchor-star {ids} at {diam}")
        same_ids += [c.ids for c in got] == [ids for ids, _ in want]
    return same_ids


def brute_force_parity(seed: int, backend, n3: int, n5: int) -> int:
    """Exact tier through the runtime on a small corpus == the oracle: the
    same ids, and diameters equal to the float32 the oracle stores."""
    ds = generate(SMALL_N, seed + 3, u=SMALL_U)
    engine = build(ds, seed)
    work = workload(ds, seed + 3, n3, n5)
    answers, _ = serve(engine, work, backend, tiers=("exact",))
    for i, (q, k) in enumerate(work):
        want = brute_force.search(ds, q, k=k).items
        got = answers[("exact", i)]
        _check(same_answer(got, want, ds, q, rel_tol=1e-6),
               f"brute-force parity failed for {q}: {got} vs {want}")
    return len(work)


def _phase(name: str, t0: float) -> None:
    _say(f"phase {name}: {time.perf_counter() - t0:.3f} s host wall-clock")


def _report(name: str, rep: dict) -> None:
    _say(f"phase {name} first query (with compile): "
         f"{rep['first_query_s']:.3f} s, steady {rep['requests'] - 1} "
         f"requests: {rep['steady_queries_s']:.3f} s host wall-clock "
         f"(device time not measured)")
    _say(f"routing[{name}]: device bins {rep['bins_device']}, host bins "
         f"{rep['bins_host']}; {rep['subsets_host']} of {rep['subsets']} "
         f"subsets served on the host")
    _say(f"dispatches[{name}]: masked-join {rep['join_dispatches']}, prune "
         f"{rep['prune_dispatches']}, h2d_bytes {rep['h2d_bytes']}, "
         f"d2h_bytes {rep['d2h_bytes']}, runtime batches {rep['batches']}")


def run_one_chip(args, n3: int = N3, n5: int = N5) -> None:
    _say(f"corpus: N={args.n} d={D} U={PAPER_SYNTH['u']} t=1 seed={args.seed}"
         + ("" if args.n == PAPER_N else
            f" (N cut from {PAPER_N} by --n)"))
    t = time.perf_counter()
    ds = generate(args.n, args.seed)
    _phase("generate", t)
    t = time.perf_counter()
    engine = build(ds, args.seed)
    _phase("build", t)
    work = workload(ds, args.seed, n3, n5)
    _say(f"queries: {n3} x 3 keywords at k=1, {n5} x 5 keywords at k=5, "
         f"tiers {','.join(TIERS)}")
    t = time.perf_counter()
    ref = reference(engine, work)
    _phase("numpy reference", t)

    runs = {"auto": "pallas",
            "pinned": PallasBackend(route="device", prune_tier="on")}
    answers, reports = {}, {}
    for name, backend in runs.items():
        answers[name], reports[name] = serve(engine, work, backend)
        _report(name, reports[name])
    pinned = reports["pinned"]
    _check(pinned["join_dispatches"] > 0 and pinned["prune_dispatches"] > 0,
           f"pinned run: masked-join dispatches {pinned['join_dispatches']}, "
           f"prune dispatches {pinned['prune_dispatches']}")
    for name in runs:
        agree = compare(answers[name], ref, ds, work, ("exact", "approx"))
        for tier, (same, ties) in agree.items():
            _say(f"answers[{name}] {tier}: {same}/{len(work)} identical to "
                 f"backend=numpy ({ties} settled a k-th-rank tie differently)")
    same_ids = check_device_tier(answers["auto"], ds, work)
    _check(all(answers["auto"][("device", i)] == answers["pinned"][("device", i)]
               for i in range(len(work))),
           "device tier differs between the two runs")
    _say(f"answers device: {len(work)}/{len(work)} agree with the float64 "
         f"anchor-star reference to 1e-5 ({same_ids} with identical ids), "
         f"identical across both runs")

    t = time.perf_counter()
    n_bf = brute_force_parity(
        args.seed, PallasBackend(route="device", prune_tier="on"), 8, 4)
    _say(f"brute force: {n_bf}/{n_bf} exact-tier answers on a {SMALL_N}-point "
         f"corpus (U={SMALL_U}) match core.brute_force.search")
    _phase("brute-force corpus", t)


def run_sharded(args, n_chips: int, n3: int = N3, n5: int = N5) -> None:
    """Sharded plane vs one device: answers bit-exact, every device
    dispatches. Exact and approx answers are settled in float64 from
    bit-exact join masks; the device tier's k sets are rescored in float64,
    so it too must match bit for bit."""
    from repro.launch.mesh import make_serving_mesh
    _say(f"corpus: N={args.n} d={D} U={PAPER_SYNTH['u']} t=1 seed={args.seed}"
         + ("" if args.n == PAPER_N else f" (N cut from {PAPER_N} by --n)")
         + f"; mesh data={n_chips}")
    t = time.perf_counter()
    ds = generate(args.n, args.seed)
    _phase("generate", t)
    t = time.perf_counter()
    eng_m = build(ds, args.seed, mesh=make_serving_mesh(data=n_chips))
    eng_1 = build(ds, args.seed)
    _phase("build two engines", t)
    work = workload(ds, args.seed, n3, n5)
    backends = {"mesh": PallasBackend(route="device", plane=eng_m.plane),
                "one": PallasBackend(route="device")}
    t = time.perf_counter()
    for tier in TIERS:
        shards = [0] * n_chips
        sharded = 0
        for k, idx in _by_k(work).items():
            qs = [work[i][0] for i in idx]
            got = eng_m.query_batch(qs, k=k, tier=tier,
                                    backend=backends["mesh"])
            st = eng_m.last_batch_stats
            shards = [a + b for a, b in zip(shards, st.shard_dispatches)]
            sharded += st.sharded_dispatches
            want = eng_1.query_batch(qs, k=k, tier=tier,
                                     backend=backends["one"])
            for q, g, w in zip(qs, got, want):
                _check(g.candidates == w.candidates,
                       f"{tier} {q}: sharded {g.candidates} != one-device "
                       f"{w.candidates}")
        _check(tier == "approx" or (sharded > 0 and all(shards)),
               f"{tier}: sharded dispatches {sharded}, per device {shards}")
        _say(f"sharded[{tier}]: {len(work)}/{len(work)} answers bit-exact "
             f"against one device; sharded dispatches {sharded}, "
             f"shard_dispatches per device {shards}")
    _phase("queries with compiles (device time not measured)", t)


# --------------------------------------------------------------------- main
def _result(ok: bool, **kw) -> str:
    return json.dumps({"ok": ok, **kw})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n", type=int, default=PAPER_N,
                    help="corpus size (the paper's setting is 1,000,000)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-plane phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(_result(False, error=f"no TPU: JAX platform is {platform!r}"))
        return 1
    if len(devices) < args.chips:
        print(_result(False, error=f"--chips {args.chips} but JAX sees "
                                   f"{len(devices)} device(s)"))
        return 1
    cache = use_compile_cache()
    _say(f"device: {devices[0].device_kind} x {len(devices)}, jax "
         f"{jax.__version__}, compile cache {cache}")
    try:
        if args.chips == 1:
            run_one_chip(args)
        else:
            run_sharded(args, args.chips)
    except Exception as e:  # every failure ends in the one result line
        traceback.print_exc()
        print(_result(False, error=f"{type(e).__name__}: {e}"[:2000]))
        return 1
    print(_result(True, device={"platform": platform,
                                "kind": devices[0].device_kind,
                                "count": len(devices)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
