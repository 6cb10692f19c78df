"""The harness finds cells, configurations, mixes and readers by name,
refuses what it does not know, and BENCHMARK.json keeps to the contract's
alphabet.  The structure tests take each benchmark of `appended.BENCHES`:
the committed one, and a copy to which a later PR's configuration, cell
and metrics are appended."""
import json
import os
import re
import subprocess
import sys

import pytest

from appended import BENCHES, CELL as APPENDED_CELL
from benchmark import flops, harness
from benchmark.peaks import peaks_for

ROOT = harness.ROOT
BENCH = BENCHES["committed"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
each_bench = pytest.mark.parametrize("bench", BENCHES.values(),
                                     ids=list(BENCHES))


def metrics_of(bench):
    return bench["end_to_end"] + bench["per_layer"]


def names_of(bench):
    return sorted({m["name"] for m in metrics_of(bench)}
                  | {w["name"] for w in bench["workloads"]}
                  | {c["name"] for c in bench["configs"]}
                  | {w["traffic"] for w in bench["workloads"]}
                  | {k for c in bench["configs"] for k in c["reduced"]})


def every(items, label=lambda x: x["name"]):
    """Parameters (which benchmark, one of its `items`), each case
    named after both."""
    return [pytest.param(which, x, id=f"{which}-{label(x)}")
            for which, bench in BENCHES.items() for x in items(bench)]


@each_bench
def test_top_level_keys_are_exactly_the_contracts(bench):
    assert sorted(bench) == ["command", "configs", "end_to_end", "paths",
                             "per_layer", "run_seconds", "workloads"]
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["paths"] == ["benchmark", "tests/benchmark"]


@pytest.mark.parametrize("which, name", every(names_of, str))
def test_names_use_the_allowed_characters(which, name):
    assert NAME.match(name), name


@pytest.mark.parametrize("which, metric", every(metrics_of))
def test_metric_entries(which, metric):
    bench = BENCHES[which]
    cells = [w["name"] for w in bench["workloads"]]
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in bench["end_to_end"]:
        assert set(metric) <= allowed | {"bound"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        assert metric["moves"] in {m["name"] for m in bench["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    for w in metric.get("workloads", []):
        assert w in cells


@each_bench
def test_no_two_share_a_name(bench):
    for group in (metrics_of(bench), bench["workloads"], bench["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


@each_bench
def test_four_chip_cells_stay_within_their_quota(bench):
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_files_under_paths_use_the_allowed_characters():
    for path in BENCH["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if not f.endswith(".pyc"):
                    assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


@pytest.mark.parametrize("which, cell", every(
    lambda bench: [w["name"] for w in bench["workloads"]], str))
def test_a_cell_is_found_with_its_files_and_readers(which, cell, roots):
    spec = harness.load_cell(cell, root=roots[which])
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


@pytest.mark.parametrize("which, config", every(lambda b: b["configs"]))
def test_a_configuration_file_states_what_it_is(which, config, roots):
    assert config["file"].startswith("benchmark/configs/")
    cfg = json.load(open(os.path.join(roots[which], config["file"])))
    assert cfg["name"] == config["name"]
    assert cfg["reduced"] == config["reduced"]
    assert cfg["reference"].startswith("benchmark.references.")
    assert harness.load_reference(cfg["reference"]).init_weights
    assert any(w["config"] == config["name"]
               for w in BENCHES[which]["workloads"])
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


def test_an_appended_cell_reads_its_metrics_through_readers_that_are_there(
        roots):
    """A later PR's cell with a new suffix: `load_cell` hands it its
    own entries and no other cell's, `read_per_layer` reads them."""
    spec = harness.load_cell(APPENDED_CELL, root=roots["appended"])
    assert {m["name"] for m in spec["end_to_end"]} \
        == {"ttft_p95_ms", "setup_s"}
    assert [m["name"] for m in spec["per_layer"]] \
        == ["serve_mfu.long", "device_idle_share.long"]
    assert spec["config"]["vocab_size"] == 163840
    ops = [("fusion.1", 0.0, 2.0), ("fusion.2", 6.0, 2.0)]
    run = {"tokens_processed": 1000, "quiet_s": 0.5, "chips": 1,
           "config": spec["config"], "peaks": peaks_for("TPU v5 lite"),
           "trace": {"devices": {0: {"ops": ops, "modules": []}},
                     "spans": []}}
    got = harness.read_per_layer(spec, run)
    assert got["device_idle_share.long"] == {"value": 50.0, "unit": "%"}
    assert got["serve_mfu.long"]["value"] == pytest.approx(
        100 * 2 * flops.matmul_params(spec["config"]) * 1000 / 0.5 / 197e12)
    for cell in CELLS:                  # and nobody else's line gains one
        mine = harness.load_cell(cell, root=roots["appended"])
        assert [m["name"] for m in mine["per_layer"]] == [
            m["name"] for m in harness.load_cell(cell)["per_layer"]]


def test_a_reader_that_reads_nothing_is_left_out():
    spec = harness.load_cell(CELLS[0])
    # no spans either: the recorder may hold an earlier test's
    assert harness.read_per_layer(
        spec, {"trace": None, "program_spans": None}) == {}


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
