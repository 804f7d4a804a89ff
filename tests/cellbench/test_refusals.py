"""main() insists on a TPU, and on the program being there."""
import os
import shutil
import subprocess
import sys

from cellbench import harness

ARGS = ["--workload", "skvbc_n4.mixed_c64_bulk1", "--seed", "3000000019",
        "--seconds", "1", "--trace", "0"]


def test_main_refuses_without_a_tpu():
    """No accelerator: non-zero exit before any work, no result."""
    r = subprocess.run([sys.executable, "-m", "cellbench.run"] + ARGS,
                       capture_output=True, text=True, timeout=300,
                       cwd=harness.ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert r.stdout == ""
    assert "needs 1 TPU chip" in r.stderr


def test_main_refuses_in_a_directory_without_the_program(tmp_path):
    """Only BENCHMARK.json and the files under `paths`: non-zero exit,
    no result."""
    manifest = harness.load_manifest()
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    for path in manifest["paths"]:
        shutil.copytree(os.path.join(harness.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "-m", "cellbench.run"] + ARGS,
                       capture_output=True, text=True, timeout=300,
                       cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
