"""bench/run.py refuses to run without a chip, and without the program."""
import json
import os
import shutil
import subprocess
import sys

from bench_fixtures import REPO

ARGS = ["--workload", "qwen2-0.5b.train-2x1024", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_refuses_without_a_chip():
    p = _run(REPO)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no accelerator" in p.stderr


def test_refuses_beside_only_its_own_files(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
