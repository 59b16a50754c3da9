"""The harness is driven by data: BENCHMARK.json, configs, mixes, readers."""
import json
import re

import pytest

import harness

BENCH = harness.load_json(harness.CHECKOUT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_CHARS = re.compile(r"^[A-Za-z0-9_.\-/]+$")


def _one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    raw = (harness.CHECKOUT / "BENCHMARK.json").read_bytes()
    assert len(raw) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(_one_line(w) for w in BENCH["command"])
    for p in BENCH["paths"]:
        assert PATH_CHARS.match(p) and not p.startswith("/") \
            and ".." not in p.split("/")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.resolve(cell, BENCH)
    w = {x["name"]: x for x in BENCH["workloads"]}[cell]
    assert c.chips == w["chips"] in (1, 4)
    assert (harness.HERE / "generators"
            / f"{c.config['generator']}.py").is_file()
    assert {t for t, _ in c.mix["tiers"]} <= {"exact", "approx", "device"}
    assert c.mix["loop"] in ("closed", "open")
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
        assert callable(harness.reader(m["name"]))


def test_config_files_are_under_paths_and_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    assert len({c["source"] for c in BENCH["configs"]}) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        cfg = harness.load_json(harness.CHECKOUT / c["file"])
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])


def test_names_and_units_use_allowed_characters():
    names = [m["name"] for m in METRICS] + CELLS \
        + [c["name"] for c in BENCH["configs"]] \
        + [w["traffic"] for w in BENCH["workloads"]]
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        assert len({x["name"] for x in group}) == len(group)
    for w in BENCH["workloads"]:
        assert _one_line(w["why"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_metric_entries():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and _one_line(m["layer"])
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


@pytest.mark.parametrize("cell", CELLS)
def test_per_layer_metric_cells_report_what_it_moves(cell):
    c = harness.resolve(cell, BENCH)
    reported = {m["name"] for m in c.end_to_end}
    for m in c.per_layer:
        assert m["moves"] in reported, (cell, m["name"])
    for m in BENCH["per_layer"]:
        for w in m.get("workloads", []):
            assert w in CELLS


def test_a_new_mix_file_is_found_without_code(tmp_path):
    mix = harness.load_json(harness.HERE / "mixes" / "device-serial.json")
    mix["tiers"] = [["approx", 1]]
    (tmp_path / "approx-serial.json").write_text(json.dumps(mix))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "synth1m-approx-serial",
                               "config": "paper-synth-1m",
                               "traffic": "approx-serial", "chips": 1,
                               "why": "test"})
    c = harness.resolve("synth1m-approx-serial", bench, mixes=tmp_path)
    assert c.mix == mix
    assert c.config["name"] == "paper-synth-1m"


def test_runtime_reader_is_window_time_outside_the_engine():
    w = harness.Window(tiers=["device"], seconds=1.0, queries=100,
                       batch_stats=[object()], engine_seconds=0.6,
                       backend={}, runtime={}, compiles=[])
    assert harness.reader("runtime_ms_per_query")(w) == 4.0
    w.batch_stats = []
    assert harness.reader("runtime_ms_per_query")(w) is None
