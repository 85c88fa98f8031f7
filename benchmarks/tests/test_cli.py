"""run.py from the outside: no TPU, no program, the manifest's shape."""

import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_fails_without_a_tpu_and_prints_no_result():
    r = _run(ROOT, "--workload", "bert-base.mlm-train", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path), "--workload", "bert-base.mlm-train", "--seed",
             "1", "--seconds", "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_unknown_workload_fails():
    r = _run(ROOT, "--workload", "no.such-cell", "--seed", "1")
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_manifest_names_files_that_exist():
    m = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cfgs = {c["name"]: c for c in m["configs"]}
    for c in m["configs"]:
        body = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            BENCH, "reference", body["family"] + ".py"))
    cells = {w["name"] for w in m["workloads"]}
    e2e = {e["name"] for e in m["end_to_end"]}
    for w in m["workloads"]:
        assert w["config"] in cfgs and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    for p in m["per_layer"]:
        assert p["moves"] in e2e and set(p["workloads"]) <= cells
        assert any(os.path.exists(os.path.join(
            BENCH, "layer_metrics", p["name"] + ext))
            for ext in (".py", ".json")), p["name"]
    assert any("mfu" in p["name"] for p in m["per_layer"])
