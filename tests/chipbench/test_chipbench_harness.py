"""The harness finds every part of every cell by name, and refuses to run
anywhere but on a TPU."""
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

import chipbench_testutil  # noqa: F401  (the repo root on sys.path)
from chipbench import harness

BENCH = harness.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_parts_load_by_name(cell):
    c = harness.find(BENCH["workloads"], cell, "workload")
    cfg = harness.load_config(c["config"])
    traffic = harness.load_traffic(c["traffic"])
    limits = harness.load_limits(cell)
    assert cfg["name"] == c["config"]
    assert cfg["precision"] == "bf16"
    fam, ref = harness.family(cfg)
    assert hasattr(fam, "build") and hasattr(ref, "forward")
    drv = harness.runner(traffic["kind"])
    assert callable(drv.run)
    assert limits and all(v > 0 for v in limits.values())
    e2e = {m["name"] for m in harness.metrics_of(BENCH, cell, "end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = harness.metrics_of(BENCH, cell, "per_layer")
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_metric(m["name"]).read)


def test_every_config_file_is_a_benchmark_config():
    for c in BENCH["configs"]:
        cfg = harness.load_config(c["name"])
        assert pathlib.Path(harness.ROOT, c["file"]).is_file()
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] == []


def test_benchmark_names_and_bounds():
    names = ([m["name"] for g in ("end_to_end", "per_layer")
              for m in BENCH[g]] + CELLS + [c["name"] for c in BENCH["configs"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert {m["name"] for m in BENCH["end_to_end"]} == {
        "serve_images_per_s", "train_step_ms", "setup_s"}
    assert sum(c["chips"] == 4 for c in BENCH["workloads"]) <= 1
    for cell in CELLS:
        e2e = {m["name"] for m in harness.metrics_of(BENCH, cell, "end_to_end")}
        layer = harness.metrics_of(BENCH, cell, "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        assert all(m["moves"] in e2e for m in layer)


def test_unknown_device_kind_is_refused():
    assert harness.peak_for("TPU v5 lite")["flops_per_s"]["bf16"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peak_for("TPU v9 imaginary")


def test_unknown_names_are_refused():
    with pytest.raises(KeyError):
        harness.find(BENCH["workloads"], "no-such-cell", "workload")
    with pytest.raises(FileNotFoundError):
        harness.load_config("no-such-config")
    with pytest.raises(FileNotFoundError):
        harness.load_metric("no-such-metric")


def _run(cwd, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", str(2 ** 31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


def test_run_without_a_tpu_fails_and_prints_no_result():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(harness.ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
