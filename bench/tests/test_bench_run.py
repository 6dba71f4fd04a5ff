"""``bench/run.py`` without a chip: it exits non-zero and prints no
result, in the checkout and in a directory that holds only the
benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

from bench import harness

ARGS = ["--workload", "qwen3-4b.sessions", "--seed", str(2**31 + 7),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            json.loads(line)
            return False
        except ValueError:
            pass
    return True


def test_refuses_without_a_tpu():
    p = _run(harness.ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_in_a_directory_of_the_benchmark_alone(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


def test_benchmark_json_names_existing_files():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for c in bench["configs"]:
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        harness.reference_of(cfg)
    for w in bench["workloads"]:
        assert (harness.BENCH / "traffic" / f"{w['traffic']}.json").exists()
    for m in bench["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name'].split('.')[0]}.py").exists()
