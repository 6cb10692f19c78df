"""The harness finds cells, configurations, mixes and readers by name,
refuses what it does not know, and BENCHMARK.json keeps to the contract's
alphabet."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_are_exactly_the_contracts():
    assert sorted(BENCH) == ["command", "configs", "end_to_end", "paths",
                             "per_layer", "run_seconds", "workloads"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]


@pytest.mark.parametrize("name", sorted(
    {m["name"] for m in METRICS} | set(CELLS)
    | {c["name"] for c in BENCH["configs"]}
    | {w["traffic"] for w in BENCH["workloads"]}
    | {k for c in BENCH["configs"] for k in c["reduced"]}))
def test_names_use_the_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in BENCH["end_to_end"]:
        assert set(metric) <= allowed | {"bound"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    for w in metric.get("workloads", []):
        assert w in CELLS


def test_no_two_share_a_name():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_four_chip_cells_stay_within_their_quota():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 4)


def test_files_under_paths_use_the_allowed_characters():
    for path in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if not f.endswith(".pyc"):
                    assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


@pytest.mark.parametrize("cell", CELLS)
def test_a_cell_is_found_with_its_files_and_readers(cell):
    spec = harness.load_cell(cell)
    assert spec["cell"]["name"] == cell
    assert spec["config"]["name"] == spec["cell"]["config"]
    assert spec["mix"]["kind"] in ("train", "serve")
    assert harness.load_driver(spec["mix"]["kind"]).run
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec["per_layer"], "every cell reports a per-layer metric"
    for m in spec["per_layer"]:
        assert callable(harness.load_reader(m["name"]))
        assert m["moves"] in e2e, \
            f"{cell} reports {m['name']} but not {m['moves']}"
    assert any("mfu" in m["name"] for m in spec["per_layer"])
    assert len(spec["cell"]["why"]) <= 200


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configuration_file_states_what_it_is(config):
    assert config["file"].startswith("benchmark/configs/")
    cfg = json.load(open(os.path.join(ROOT, config["file"])))
    assert cfg["name"] == config["name"]
    assert cfg["reduced"] == config["reduced"]
    assert cfg["reference"].startswith("benchmark.references.")
    assert harness.load_reference(cfg["reference"]).init_weights
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    for width in ("hidden_size", "intermediate_size", "head_dim"):
        assert width not in config["reduced"]


def test_unknown_names_are_refused():
    with pytest.raises(harness.BenchError):
        harness.load_cell("no-such.cell")
    with pytest.raises(harness.BenchError):
        harness.load_driver("no-such-kind")
    with pytest.raises(harness.BenchError):
        harness.load_reader("no_such_metric")
    with pytest.raises(harness.BenchError):
        harness.load_reference("paddle_tpu.text.gpt")


def test_a_split_metric_falls_back_to_its_stem():
    assert harness.load_reader("device_idle_share.some-later-cell")


def test_a_reader_that_reads_nothing_is_left_out():
    spec = harness.load_cell(CELLS[0])
    assert harness.read_per_layer(spec, {"trace": None}) == {}


def test_result_line_lists_each_number_beside_its_limit(capsys):
    spec = harness.load_cell(CELLS[0])
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1}
    e2e = {m["name"]: 1.5 for m in spec["end_to_end"]}
    line = json.loads(harness.result_line(
        spec, False, device, e2e, {}, 10, 0, [("a_gap", 0.1, 0.2)]))
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["attempted"] == 10
    assert set(line["metrics"]) == set(e2e)
    assert line["compared"] == {"a_gap": {"value": 0.1, "limit": 0.2}}
    assert "compared a_gap: value 0.1 limit 0.2 ok" in capsys.readouterr().err
    for bad in (0.3, float("nan"), float("inf"), None):
        over = json.loads(harness.result_line(
            spec, False, device, e2e, {}, 10, 0, [("a_gap", bad, 0.2)]))
        assert over["correct"] is False


def test_traced_line_carries_per_layer_metrics_and_breakdown():
    spec = harness.load_cell(CELLS[0])
    per = {"train_mfu": {"value": 60.0, "unit": "%"}}
    line = json.loads(harness.result_line(
        spec, True, {"platform": "tpu"}, {}, per, 1, 0, [],
        {"device_ops": [["x", 1.0]], "idle_gaps": []}))
    assert line["metrics"] == per and "breakdown" in line


def _run_cli(*argv, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, *argv], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_the_command_fails_off_a_tpu_and_prints_no_result():
    out = _run_cli(os.path.join("benchmark", "run.py"), "--workload",
                   CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert '"correct"' not in out.stdout


def test_the_command_refuses_an_unknown_cell():
    out = _run_cli(os.path.join("benchmark", "run.py"), "--workload", "nope")
    assert out.returncode != 0 and '"correct"' not in out.stdout


def test_without_the_program_the_command_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_cli(os.path.join("benchmark", "run.py"), "--workload",
                   CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0",
                   cwd=tmp_path)
    assert out.returncode != 0 and '"correct"' not in out.stdout
    assert "the program is not here" in out.stderr


@pytest.mark.parametrize("name, key, want", [
    ("first_token_p95_ms.chat", "ttft_ms", 95.05),
    ("first_token_mean_ms.chat", "ttft_ms", 50.5),
    ("queue_wait_p95_ms.chat", "queue_wait_ms", 95.05),
    ("engine_step_ms_p50.chat", "step_ms", 50.5)])
def test_host_clock_readers_reduce_the_harness_own_times(name, key, want):
    read = harness.load_reader(name)
    assert read({key: [float(i) for i in range(1, 101)]}) \
        == pytest.approx(want)
    assert read({key: []}) is None and read({}) is None
