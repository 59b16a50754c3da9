"""Serve-loop error paths: a bad request yields a structured error envelope
and the stream survives — in both the synchronous loop
(``handle_request_safe``) and the async runtime path
(``serve_with_runtime``). Covers the satellite checklist: malformed line,
unknown op, insert with mismatched attrs schema, tenant-unknown keyword."""
import json

import numpy as np
import pytest

from repro.data.synthetic import attach_attrs, synthetic_tenants
from repro.launch.serve import (handle_request_safe, serve_with_runtime)
from repro.serve.engine import NKSEngine
from repro.serve.runtime import RuntimeConfig, ServingRuntime


@pytest.fixture(scope="module")
def engine():
    ds = attach_attrs(synthetic_tenants({"acme": 120, "globex": 80},
                                        d=4, u=16, t=2, seed=2), seed=2)
    return NKSEngine(ds, seed=1, compact_min=10_000)


BAD_REQUESTS = [
    # (request, expected op in envelope, error fragment)
    ({"__parse_error__": "malformed JSON: boom"}, "parse", "malformed"),
    ("not a dict", "parse", "JSON object"),
    ({"op": "frobnicate"}, "frobnicate", "unknown op"),
    ({"op": "query"}, "query", "keywords"),                  # missing field
    ({"keywords": [99999]}, "query", ""),                    # out-of-dict kw
    # attrs schema mismatch: corpus has price+category, insert omits one
    ({"op": "insert", "points": [[0.0] * 4], "keywords": [[0]],
      "attrs": {"price": [1.0]}, "tenant": "acme"}, "insert", ""),
    # tenant-unknown keyword: local id beyond the tenant's namespace
    ({"op": "insert", "points": [[0.0] * 4], "keywords": [[4000]],
      "tenant": "acme"}, "insert", ""),
    # unknown tenant name
    ({"op": "insert", "points": [[0.0] * 4], "keywords": [[0]],
      "tenant": "hooli"}, "insert", ""),
    # snapshot without a WAL attached
    ({"op": "snapshot"}, "snapshot", "WAL"),
]

GOOD = {"keywords": [0, 1], "k": 1, "filter": {"tenant": "acme"}}


def _check_envelope(out, op, frag):
    assert out.get("status", "ok") == "error" or "error" in out
    assert out["op"] == op
    assert frag.lower() in out["error"].lower()


def test_sync_loop_survives_every_bad_request(engine):
    for req, op, frag in BAD_REQUESTS:
        out = handle_request_safe(engine, req, tier="exact", k=1)
        _check_envelope(out, op, frag)
        # the stream is alive: a good request right after still answers
        ok = handle_request_safe(engine, GOOD, tier="exact", k=1)
        assert "error" not in ok and ok["results"]


def test_runtime_loop_survives_every_bad_request(engine):
    reqs = []
    for req, _, _ in BAD_REQUESTS:
        reqs.append(req)
        reqs.append(GOOD)
    rt = ServingRuntime(engine, RuntimeConfig(batch_window_s=0.0))
    try:
        outs = list(serve_with_runtime(rt, engine, reqs, tier="exact", k=1))
    finally:
        rt.close()
    assert len(outs) == len(reqs)
    for i, (_, op, frag) in enumerate(BAD_REQUESTS):
        _check_envelope(outs[2 * i], op, frag)
        assert "error" not in outs[2 * i + 1] and outs[2 * i + 1]["results"]
    # no bad request crashed the runtime itself
    assert not rt.health()["crashed"]


def test_sync_and_runtime_answers_agree(engine):
    """The two serving paths format identical results for the same stream
    (modulo latency), including tenant-resolved inserts."""
    rng = np.random.default_rng(8)
    stream = [
        {"keywords": [0, 1], "k": 2, "filter": {"tenant": "acme"}},
        {"op": "insert",
         "points": rng.standard_normal((3, 4)).astype(np.float32).tolist(),
         "keywords": [[0, 1]] * 3,
         "attrs": {"price": [1.0, 2.0, 3.0], "category": [0, 1, 0]},
         "tenant": "acme"},
        {"keywords": [0, 1], "k": 3, "filter": {"tenant": "acme"}},
        {"op": "delete", "ids": [0]},
        {"keywords": [0, 1], "k": 3, "filter": {"tenant": "acme"}},
        {"op": "health"},
    ]

    def strip(out):
        out = {k: v for k, v in out.items() if k != "latency_ms"}
        return out

    ds = engine.dataset
    sync_eng = NKSEngine(ds, seed=1, compact_min=10_000)
    sync = [strip(handle_request_safe(sync_eng, r, tier="exact", k=1))
            for r in stream]
    rt_eng = NKSEngine(ds, seed=1, compact_min=10_000)
    rt = ServingRuntime(rt_eng, RuntimeConfig(batch_window_s=0.0))
    try:
        asynchronous = [strip(o) for o in
                        serve_with_runtime(rt, rt_eng, stream,
                                           tier="exact", k=1)]
    finally:
        rt.close()
    # health payloads legitimately differ (queue stats); compare the rest.
    for s, a in zip(sync[:-1], asynchronous[:-1]):
        assert s == a
    assert asynchronous[-1]["op"] == "health"
    assert asynchronous[-1]["generation"] == sync[-1]["generation"]


def _launch(monkeypatch, capsys, *argv):
    from repro.launch import serve as launcher
    monkeypatch.setattr(launcher, "use_compile_cache", lambda: None)
    monkeypatch.setattr("sys.argv", ["serve", *argv])
    launcher.main()
    out = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    return [{k: v for k, v in o.items() if k != "latency_ms"} for o in out]


def test_launcher_backend_pallas_answers_like_numpy(monkeypatch, capsys):
    """``--runtime --backend pallas`` serves the exact tier through the join
    kernels (the XLA lowering off-TPU) with the numpy route's answers."""
    argv = ("--runtime", "--corpus", "uniform", "--n", "600", "--d", "4",
            "--u", "12", "--t", "2", "--tier", "exact", "--k", "2",
            "--queries", "5")
    numpy_out = _launch(monkeypatch, capsys, *argv, "--backend", "numpy")
    pallas_out = _launch(monkeypatch, capsys, *argv, "--backend", "pallas")
    assert len(numpy_out) == 5 and all(o["results"] for o in numpy_out)
    assert pallas_out == numpy_out


def test_launcher_backend_needs_runtime(monkeypatch, capsys):
    with pytest.raises(SystemExit) as exc:
        _launch(monkeypatch, capsys, "--backend", "pallas")
    assert exc.value.code == 2
