"""``BENCHMARK.json``: every name resolves to its files, and the file keeps
to the shape the benchmark's contract gives it."""

import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    assert SPEC["command"] == ["python3", "bench/run.py"]
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p


def test_names_are_unique_and_well_formed():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[section]]
        assert len(names) == len(set(names)), section
        assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files_resolve(cfg):
    path = ROOT / cfg["file"]
    assert path.is_file() and path.parts[-2] == "configs"
    conf = json.loads(path.read_text())
    assert path.stem == cfg["name"]
    assert conf["reduced"] == cfg["reduced"]
    assert (ROOT / "bench" / "kinds" / f"{conf['kind']}.py").is_file()
    if conf["kind"] == "serve":
        # the plain reference the configuration names, found by name
        from bench.kinds import serve

        ref = serve.reference_of(conf)
        assert ref.__file__ == str(ROOT / "bench" / "reference"
                                   / f"{conf['reference']}.py")
        for fn in ("make_weights", "program_params", "logits_at"):
            assert callable(getattr(ref, fn))


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workloads_resolve(w):
    assert w["config"] in {c["name"] for c in SPEC["configs"]}
    assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    assert w["chips"] in (1, 4)
    assert w["name"] == f"{w['config']}.{w['traffic']}"
    conf = json.loads((ROOT / "bench" / "configs" / f"{w['config']}.json").read_text())
    mix = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    assert {**conf.get("limits", {}), **mix.get("limits", {})}, \
        "every cell states the limits of its check"
    if conf["kind"] == "serve":
        assert (ROOT / "bench" / "generators" / f"{mix['generator']}.py").is_file()
    assert 1 <= len(w["why"]) <= 200
    # every cell reports set-up, another end-to-end metric and a per-layer one
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if w["name"] in m.get("workloads", [w["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(w["name"] in m.get("workloads", [w["name"]])
               for m in SPEC["per_layer"])


def test_pairs_of_config_and_traffic_are_unique():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_end_to_end_metrics():
    names = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", names)) <= names
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metrics_resolve(m):
    from bench.harness import reader_path

    assert reader_path(m["name"]).is_file()
    assert UNIT.match(m["unit"]) and m["source"] in SOURCES
    assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    moved = [e for e in SPEC["end_to_end"] if e["name"] == m["moves"]]
    assert moved, f"{m['name']} moves an unknown metric"
    cells = set(moved[0].get("workloads", {w["name"] for w in SPEC["workloads"]}))
    assert set(m["workloads"]) <= cells, "each listed cell reports what it moves"


def test_layer_names_agree():
    """Metrics of one layer name it letter for letter."""
    layers = {}
    for m in SPEC["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
