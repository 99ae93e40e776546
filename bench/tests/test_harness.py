"""The harness resolves everything from files by name, refuses what the
contract refuses, and prints the result line in its shape."""

from __future__ import annotations

import copy
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.trace import TraceSummary

ROOT = harness.ROOT
SPEC = harness.load_json(ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_resolves_from_its_files(name):
    cell = harness.resolve_cell(SPEC, name)
    assert cell.config["arch"] and cell.traffic["kind"]
    assert harness.driver_for(cell).run
    assert harness.flops_for(cell.config["family"])
    ref = cell.reference
    assert callable(ref.Sizes.of) and ref.CONTROLS
    assert callable(ref.train_steps if cell.traffic["kind"] == "train"
                    else ref.served_gaps)
    for m in cell.per_layer:
        mod = harness.load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)
    assert "setup_s" in {m["name"] for m in cell.end_to_end}


def test_config_files_state_what_benchmark_json_says():
    for c in SPEC["configs"]:
        cfg = harness.load_json(ROOT / c["file"])
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert cfg[key] != cfg["source_values"][key], key


def _copy_tree(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def test_a_new_cell_needs_only_new_files_and_an_entry(tmp_path):
    root = _copy_tree(tmp_path)
    spec = copy.deepcopy(SPEC)
    traffic = harness.load_json(root / "bench/traffic/code_lake_s3.json")
    traffic["corpus"]["layout_seed"] = 7
    (root / "bench/traffic/code_lake_s3_b.json").write_text(json.dumps(traffic))
    (root / "bench/metrics/steps_seen.py").write_text(
        "def read(rec):\n    return rec.layer.get('steps')\n")
    spec["workloads"].append({"name": "sc2-train-lake-s3-b",
                              "config": "starcoder2-3b-l8-train",
                              "traffic": "code_lake_s3_b", "chips": 1,
                              "why": "a test cell"})
    spec["per_layer"].append({"name": "steps_seen", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "train step",
                              "moves": "train_tokens_per_s",
                              "workloads": ["sc2-train-lake-s3-b"]})
    for m in spec["end_to_end"]:
        if "sc2-train-lake-s3" in m.get("workloads", []):
            m["workloads"].append("sc2-train-lake-s3-b")
    cell = harness.resolve_cell(spec, "sc2-train-lake-s3-b", root)
    assert cell.traffic["corpus"]["layout_seed"] == 7
    assert [m["name"] for m in cell.per_layer] == ["steps_seen"]
    rec = harness.Record(metrics={}, attempted=1, failed=0,
                         memory_peak_bytes=0, checks={}, layer={"steps": 3})
    assert harness.read_per_layer(cell, rec, root)["steps_seen"]["value"] == 3


@pytest.mark.parametrize("reference,error", [
    (None, "names no valid reference"),
    ("no_such_model", "no such file"),
])
def test_a_config_naming_no_reference_or_a_missing_one_is_refused(
        tmp_path, reference, error):
    root = _copy_tree(tmp_path)
    path = root / "bench/configs/starcoder2-3b-l8-train.json"
    cfg = harness.load_json(path)
    cfg.pop("reference")
    if reference is not None:
        cfg["reference"] = reference
    path.write_text(json.dumps(cfg))
    with pytest.raises(harness.BenchError, match=error):
        harness.resolve_cell(SPEC, "sc2-train-lake-s3", root)


def test_a_metric_whose_end_to_end_metric_the_cell_lacks_is_refused():
    spec = copy.deepcopy(SPEC)
    spec["per_layer"].append({"name": "train_mfu_on_serve", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "train step",
                              "moves": "train_tokens_per_s",
                              "workloads": ["sc2-serve-code-c448"]})
    with pytest.raises(harness.BenchError, match="does not report"):
        harness.resolve_cell(spec, "sc2-serve-code-c448")
    spec = copy.deepcopy(SPEC)
    spec["per_layer"].append({"name": "everywhere", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "train step",
                              "moves": "train_tokens_per_s"})
    with pytest.raises(harness.BenchError, match="does not report"):
        harness.resolve_cell(spec, "sc2-serve-code-c448")


def test_unknown_cells_and_peaks_are_errors():
    with pytest.raises(harness.BenchError, match="unknown workload"):
        harness.resolve_cell(SPEC, "no-such-cell")
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.peaks("TPU v9000")
    assert harness.peaks("TPU v5 lite")["peak_flops_bf16"] == 197e12


def _record(**kw):
    base = dict(metrics={"serve_tokens_per_s": 700.5, "setup_s": 12.25},
                attempted=320, failed=0,
                memory_peak_bytes=123,
                checks={"logit_gap": {"value": 0.01, "limit": 0.1}},
                layer={"kind": "serve", "ttft_s": [2.5, 2.7],
                       "token_gaps_s": [0.01] * 40},
                trace=TraceSummary(busy_s=9.0, window_s=10.0,
                                   top_ops=[("fusion.1", 4.0)],
                                   idle_gaps=[("bench.generate", 0.5)]))
    base.update(kw)
    return harness.Record(**base)


@pytest.mark.parametrize("trace", [False, True])
def test_the_last_line_has_the_contract_shape(trace):
    cell = harness.resolve_cell(SPEC, "sc2-serve-code-c448")
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = harness.result_line(cell, _record(), trace, dev)
    keys = list(line)
    assert keys[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert keys[-1] == "checks"
    assert line["correct"] is True
    assert line["device"]["memory_peak_bytes"] == 123
    json.dumps(line)
    if trace:
        assert line["device"]["busy_s"] == 9.0
        assert line["device"]["window_s"] == 10.0
        assert line["breakdown"]["device_ops"] == [["fusion.1", 4.0]]
        assert line["metrics"]["serve_idle_share"]["value"] == pytest.approx(10)
        assert line["metrics"]["ttft_ms_p50"]["value"] == pytest.approx(2600)
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        assert line["metrics"]["setup_s"] == {"value": 12.25, "unit": "s"}


def test_a_check_over_its_limit_or_a_failure_is_not_correct():
    over = {"logit_gap": {"value": 0.2, "limit": 0.1}}
    assert not _record(checks=over).correct
    assert not _record(failed=1).correct


def test_spans_of_steps_last_a_quarter_second_at_least():
    ends = [0.2 * (i + 1) for i in range(10)]
    spans = harness.spans_of(ends, 0.0)
    assert spans == pytest.approx([0.2] * 5)


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", "sc2-train-lake-s3", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def test_no_tpu_no_result():
    got = _run(ARGS, ROOT)
    assert got.returncode != 0
    assert "needs a TPU" in got.stderr
    assert got.stdout.strip() == ""


def test_only_the_benchmark_files_no_result(tmp_path):
    root = _copy_tree(tmp_path)
    shutil.copytree(ROOT / "bench" / "tests", root / "bench" / "tests")
    got = _run(ARGS, root)
    assert got.returncode != 0
    assert "No module named 'repro'" in got.stderr
    assert not any(ln.startswith("{") for ln in got.stdout.splitlines())
