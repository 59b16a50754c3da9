"""One general traffic generator, driven by a mix file (``mixes/*.json``).

A mix names its tiers, the query shape (``q`` keywords, top ``k``), the
loop and its parameters. ``tiers`` lists [tier, count] pairs: every block of
sum(count) consecutive requests holds each tier that many times, in an
order drawn from the seed. The loops:

* ``"loop": "closed"`` — one caller who sends the next request when the
  previous one is answered (a search box whose user waits);
* ``"loop": "open"`` — arrivals at ``rate_qps`` whatever the service does.
  A run of ``s`` seconds has exactly round(rate * s) arrivals, placed as the
  order statistics of uniform times: a Poisson process given its count, so
  every seed offers the same load in another order.

Keywords are drawn uniformly from the populated keywords (the paper's §VIII
method). No query repeats within a run: the warm-up and the window draw from
separate streams of one seed, and a query seen before is drawn again.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator

import numpy as np

RESULT_WAIT_S = 60.0     # how long past the close a late answer is awaited


class QueryStream:
    """Endless distinct q-keyword queries (sorted keyword lists)."""

    def __init__(self, populated: np.ndarray, mix: dict,
                 rng: np.random.Generator, seen: set):
        self.q = int(mix["q"])
        self.rng = rng
        self.seen = seen
        self.keywords = populated

    def __iter__(self) -> Iterator[list[int]]:
        return self

    def __next__(self) -> list[int]:
        while True:
            q = sorted(self.rng.choice(self.keywords, size=self.q,
                                       replace=False).tolist())
            if tuple(q) not in self.seen:
                self.seen.add(tuple(q))
                return q


class TierPlan:
    """The tier of each request, in shuffled blocks of the mix's counts."""

    def __init__(self, mix: dict, rng: np.random.Generator):
        self.block = [t for t, n in mix["tiers"] for _ in range(int(n))]
        self.rng = rng
        self._pending: list[str] = []

    def __next__(self) -> str:
        if not self._pending:
            self._pending = [self.block[i] for i in
                             self.rng.permutation(len(self.block))]
        return self._pending.pop()


@dataclasses.dataclass
class Record:
    query: list[int]
    tier: str
    due: float               # when the request was due (monotonic)
    sent: float              # when it was submitted
    ticket: object

    @property
    def response(self):
        return self.ticket.response

    @property
    def done(self) -> float | None:
        r = self.ticket.response
        return None if r is None else self.ticket.submitted_at + r.latency_s

    @property
    def served(self) -> bool:
        r = self.ticket.response
        return r is not None and r.ok and not r.degraded


@dataclasses.dataclass
class LoopResult:
    records: list[Record]
    start: float
    end: float               # last completion (or the close, if later)

    @property
    def seconds(self) -> float:
        return self.end - self.start


def request(mix: dict, q: list[int], tier: str) -> dict:
    return {"op": "query", "keywords": q, "k": int(mix["k"]), "tier": tier}


def _await(records: list[Record], until: float) -> None:
    for r in records:
        left = until - time.monotonic()
        if r.ticket.done() or left <= 0:
            continue
        try:
            r.ticket.result(timeout=left)
        except TimeoutError:
            pass


def closed_loop(rt, mix: dict, stream: QueryStream, tiers: TierPlan, *,
                seconds: float = None, count: int = None,
                span: Callable = None) -> LoopResult:
    """One client: the next request goes when the last one is answered.
    Runs for ``seconds`` (the request in flight at the close finishes) or
    for ``count`` requests."""
    records = []
    start = time.monotonic()
    while True:
        now = time.monotonic()
        if seconds is not None and now - start >= seconds:
            break
        if count is not None and len(records) >= count:
            break
        q, tier = next(stream), next(tiers)
        with span("chipbench.client"):
            t = rt.submit(request(mix, q, tier))
            rec = Record(q, tier, now, now, t)
            records.append(rec)
            try:
                t.result(timeout=RESULT_WAIT_S)
            except TimeoutError:
                break
    end = max([start] + [r.done for r in records if r.done is not None])
    return LoopResult(records, start, end)


def arrival_times(mix: dict, seconds: float, rng: np.random.Generator
                  ) -> np.ndarray:
    """Offsets of round(rate * seconds) arrivals in [0, seconds)."""
    n = max(1, int(round(float(mix["rate_qps"]) * seconds)))
    return np.sort(rng.uniform(0.0, seconds, size=n))


def open_loop(rt, mix: dict, stream: QueryStream, tiers: TierPlan,
              offsets: np.ndarray, *, span: Callable = None) -> LoopResult:
    """Submit each request when it is due, whatever the service does; then
    wait for every answer, up to a minute past the close."""
    queries = [(next(stream), next(tiers)) for _ in offsets]
    records = []
    start = time.monotonic() + 0.005
    with span("chipbench.generator"):
        for off, (q, tier) in zip(offsets, queries):
            due = start + float(off)
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            t = rt.submit(request(mix, q, tier))
            records.append(Record(q, tier, due, time.monotonic(), t))
    close = start + float(offsets[-1])
    with span("chipbench.drain"):
        _await(records, time.monotonic() + RESULT_WAIT_S)
    end = max([close] + [r.done for r in records if r.done is not None])
    return LoopResult(records, start, end)


def run_loop(rt, mix: dict, stream: QueryStream, tiers: TierPlan, rng, *,
             seconds=None, count=None, span=None) -> LoopResult:
    if mix["loop"] == "closed":
        return closed_loop(rt, mix, stream, tiers, seconds=seconds,
                           count=count, span=span)
    if mix["loop"] == "open":
        if count is not None:
            offsets = arrival_times(mix, count / float(mix["rate_qps"]), rng)
        else:
            offsets = arrival_times(mix, seconds, rng)
        return open_loop(rt, mix, stream, tiers, offsets, span=span)
    raise ValueError(f"unknown loop {mix['loop']!r}")


def per_query_s(res: LoopResult, mix: dict) -> float:
    """The statistic the warm-up watches: wall time per request (closed
    loop) or mean latency from due (open loop)."""
    if mix["loop"] == "closed":
        return res.seconds / max(len(res.records), 1)
    lat = [r.done - r.due for r in res.records if r.done is not None]
    return float(np.mean(lat)) if lat else float("inf")


def warm_up(rt, mix: dict, stream: QueryStream, tiers: TierPlan, rng,
            span) -> dict:
    """Whole queries of the cell's own mix, in chunks, until the per-query
    time of two successive chunks agrees within ``tol``."""
    w = mix["warmup"]
    times, queries = [], 0
    for _ in range(int(w["max_chunks"])):
        res = run_loop(rt, mix, stream, tiers, rng, count=int(w["chunk"]),
                       span=span)
        queries += len(res.records)
        times.append(per_query_s(res, mix))
        if len(times) >= int(w["min_chunks"]) and \
                abs(times[-1] - times[-2]) <= float(w["tol"]) * times[-2]:
            return {"queries": queries, "chunks": times, "steady": True}
    return {"queries": queries, "chunks": times, "steady": False}


def latency_ms(res: LoopResult) -> np.ndarray:
    """Latency of every request from when it was due, in ms. A request not
    served (refused, failed, degraded or never answered) counts as late as
    the longest wait the run allowed."""
    cap = res.end + RESULT_WAIT_S
    return np.array([(r.done - r.due) * 1e3 if r.served
                     else (cap - r.due) * 1e3 for r in res.records])


def percentile(values: np.ndarray, p: float) -> float:
    """Nearest-rank percentile (the value of rank ceil(p/100 * n))."""
    v = np.sort(values)
    return float(v[max(0, int(np.ceil(p / 100.0 * len(v))) - 1)])


def quarter_rates(res: LoopResult) -> list[float]:
    """Completions per second in each quarter of the window."""
    edges = np.linspace(res.start, res.end, 5)
    done = np.array([r.done for r in res.records if r.done is not None])
    counts, _ = np.histogram(done, bins=edges)
    return (counts / np.diff(edges)).tolist()
