"""BENCHMARK.json against the contract's rules that a file can be held to,
and against the files under chipbench/ that its names point at."""

import importlib
import json
import os
import re

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def test_keys_names_and_units(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["chipbench"]
    assert 1 <= manifest["run_seconds"] <= 51
    names = ([c["name"] for c in manifest["configs"]]
             + [w["name"] for w in manifest["workloads"]]
             + [w["traffic"] for w in manifest["workloads"]]
             + [m["name"] for m in manifest["end_to_end"]]
             + [m["name"] for m in manifest["per_layer"]]
             + [k for c in manifest["configs"] for k in c["reduced"]])
    for name in names:
        assert NAME.match(name), name
    for section in ("end_to_end", "per_layer"):
        seen = [m["name"] for m in manifest[section]]
        assert len(seen) == len(set(seen))
        for m in manifest[section]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]
    for entry in manifest["workloads"]:
        assert len(entry["why"]) <= 200 and entry["chips"] in (1, 4)
    assert len(json.dumps(manifest)) < 64 * 1024


def test_every_cell_reports_what_its_layer_metrics_move(manifest):
    cells = [w["name"] for w in manifest["workloads"]]

    def reported_in(metric):
        return set(metric.get("workloads", cells))

    e2e = {m["name"]: reported_in(m) for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert reported_in(m) <= e2e[m["moves"]], m["name"]
        assert reported_in(m) <= set(cells)
    for cell in cells:
        own = [n for n, where in e2e.items() if cell in where]
        assert "setup_s" in own and len(own) >= 2, cell
        assert any(cell in reported_in(m) for m in manifest["per_layer"])


def test_every_name_has_its_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    used = set()
    for w in manifest["workloads"]:
        used.add(w["config"])
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json")), w
        limits = load("limits", w["name"] + ".json")
        assert limits and all("limit" in v for v in limits.values())
    assert used == set(configs), "a configuration without a cell"
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        assert c["file"].startswith("chipbench/configs/")
        conf = json.load(open(os.path.join(ROOT, c["file"])))
        importlib.import_module("chipbench.runners." + conf["runner"])
        for key in c["reduced"]:
            assert key in conf, (c["name"], key)
    layers = set()
    for m in manifest["per_layer"]:
        spec = load("layer_metrics", m["name"] + ".json")
        assert spec["layer"] == m["layer"]
        layers.add(m["layer"])
        reader = importlib.import_module(
            "chipbench.readers." + spec["reader"])
        assert callable(reader.read)
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md does not list the layer {layer!r}"


def test_peaks_name_their_source():
    peaks = load("peaks.json")
    row = peaks["TPU v5 lite"]
    assert row["flops_per_s_bf16"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "source" in row


def test_a_run_without_a_tpu_exits_non_zero_and_prints_no_result(capsys):
    from chipbench import run

    cell = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))[
        "workloads"][0]["name"]
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", cell, "--seed", "3000000000",
                  "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value.code)
    assert '"metrics"' not in capsys.readouterr().out


def test_a_configuration_states_its_kernel_mode_or_gets_the_default():
    import simple_tensorflow_tpu as stf
    from chipbench import harness

    default = stf.kernels.default_mode()
    try:
        for config in (load("configs", f) for f in
                       sorted(os.listdir(os.path.join(BENCH, "configs")))):
            stated = config["program"].get("kernel_mode")
            assert stated in (None,) + tuple(stf.kernels.MODES)
            harness.set_kernel_mode(config)
            assert stf.kernels.default_mode() == (stated or default)
        assert set(harness.kernel_routing()) == {
            "mode", "routed", "fallback", "flash_tiles"}
    finally:
        stf.kernels.set_mode(None)
