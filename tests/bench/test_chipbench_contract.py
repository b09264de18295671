"""BENCHMARK.json is well formed and every name in it resolves to a file;
the command refuses to run without a chip or without the program."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path


ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_names_and_units():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_every_name_resolves_to_a_file():
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert all(k in conf for k in c["reduced"] if k != "max_batch")
    for w in BENCH["workloads"]:
        assert (ROOT / "chipbench" / "traffic" /
                (w["traffic"] + ".json")).exists()
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (ROOT / "chipbench" / "metrics" / (m["name"] + ".py")).exists()


def test_every_cell_reports_what_it_must():
    sys.path.insert(0, str(ROOT))
    from chipbench import run
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        cell = run.load_cell(w["name"], str(ROOT))
        assert "setup_s" in cell.end_to_end and len(cell.end_to_end) >= 2
        assert cell.per_layer
        for m in BENCH["per_layer"]:
            if w["name"] in m.get("workloads", ()):
                assert m["moves"] in e2e and m["moves"] in cell.end_to_end


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = _run(ROOT, env)
    assert r.returncode == 2 and r.stdout.strip() == ""
    assert "needs 1 TPU" in r.stderr


def test_no_program_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = _run(tmp_path, env)
    assert r.returncode != 0 and r.stdout.strip() == ""
