"""BENCHMARK.json is data the harness finds its way through: every name
in it resolves to a file of its own, and a new cell is a new mix file and
an entry, with no file edited."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from bench import run

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_name_resolves_to_a_file():
    cells = {w["name"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        path = ROOT / c["file"]
        assert path.is_file() and path.parts[len(ROOT.parts)] in ("bench",)
        cfg = json.loads(path.read_text())
        assert cfg["name"] == c["name"]
        assert (ROOT / "bench" / "arch" / f"{cfg['arch']}.py").is_file()
        assert cfg["limits"]["logit_gap"] is not None
    for w in SPEC["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert run.metric_file(m["name"]).is_file()
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        if "_roofline" in m["name"]:
            kernel = m["name"].split("_roofline")[0]
            assert (ROOT / "bench" / "kernels" / f"{kernel}.py").is_file()


def test_every_cell_reports_what_it_must():
    for w in SPEC["workloads"]:
        def reports(group):
            return {m["name"] for m in SPEC[group]
                    if w["name"] in m.get("workloads", [w["name"]])}
        e2e = reports("end_to_end")
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = reports("per_layer")
        assert layer
        moved = {m["moves"] for m in SPEC["per_layer"] if m["name"] in layer}
        assert moved <= e2e


def test_new_cell_from_files_alone(tmp_path):
    """A copy of the checkout gains a configuration file, a mix file and
    a workload entry; its cell runs (the look for a chip aside) without
    any file of the harness being edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    shutil.copy(DATA / "tiny.json", tmp_path / "bench/configs/tiny.json")
    shutil.copy(DATA / "tiny-chat.json",
                tmp_path / "bench/traffic/tiny-chat.json")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.chat", "config": "tiny",
                              "traffic": "tiny-chat", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "tpot_p90_ms"):
            m["workloads"].append("tiny.chat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    launcher = (
        "import sys; sys.path.insert(0, sys.argv[1]); from bench import run;"
        "run.device_info = lambda jax, chips: {'platform': 'cpu', "
        "'kind': 'TPU v5 lite', 'count': chips};"
        "sys.exit(run.main(sys.argv[2:]))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / ".jax_cache"))
    out = subprocess.run(
        [sys.executable, "-c", launcher, str(tmp_path), "--workload",
         "tiny.chat", "--seed", str(2**31 + 3), "--seconds", "2",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert res["device"]["memory_peak_bytes"] >= 0


def test_no_result_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    gives no result and a non-zero exit."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    w = SPEC["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", w, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert not out.stdout.strip()


def test_no_result_without_a_chip():
    """On the CPU the harness refuses before any work: exit 2, no line."""
    w = SPEC["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", w, "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert out.returncode == 2, out.stderr[-2000:]
    assert not out.stdout.strip()
