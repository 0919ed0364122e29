"""The manifest and the files it names: every name resolves, every name
and unit is made of the allowed characters, every per-layer metric moves
an end-to-end metric its cells report, and an unknown device is refused."""

import os
import re
import shutil
import subprocess
import sys

import pytest

import harness
from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
MAN = harness.manifest(ROOT)
E2E = {m["name"]: m for m in MAN["end_to_end"]}
CELLS = {w["name"]: w for w in MAN["workloads"]}


def test_manifest_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["bench"]
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert 1 <= MAN["run_seconds"] <= 51
    cells = len(MAN["workloads"])
    assert cells <= 24 and all(w["chips"] == 1 for w in MAN["workloads"])


def test_every_name_resolves_to_its_files():
    for c in MAN["configs"]:
        assert c["file"].startswith("bench/")
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
    for w in MAN["workloads"]:
        r = harness.resolve(ROOT, w["name"])
        assert r["traffic"]["mode"] in ("path", "cv", "closed")
        assert "kkt_worst" in r["limits"]
    for m in MAN["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def test_names_and_units_use_allowed_characters():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in MAN[k]]
    assert len(names) == len(set(names))
    names += [w["config"] for w in MAN["workloads"]]
    names += [w["traffic"] for w in MAN["workloads"]]
    names += [k for c in MAN["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for text in ([w["why"] for w in MAN["workloads"]]
                 + [c["why"] for c in MAN["configs"]]
                 + [m["layer"] for m in MAN["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_bounds_and_reporting():
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in CELLS:
        e2e = [m for m in MAN["end_to_end"]
               if w in m.get("workloads", [w])]
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert any(w in m.get("workloads", [w]) for m in MAN["per_layer"])


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    for m in MAN["per_layer"]:
        moved = E2E[m["moves"]]
        for w in m["workloads"]:
            assert w in CELLS
            assert w in moved.get("workloads", [w]), (m["name"], w)


def test_unknown_device_kind_raises(tmp_path):
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.SetupError):
        harness.peaks_for("TPU v9 imaginary")


def _run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "direct_pgn",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_exits_nonzero_without_a_result():
    p = _run_bench(ROOT)
    assert p.returncode == 3, p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_bench(tmp_path)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
