"""The command refuses to run without a chip, and without the program."""
import os
import shutil
import subprocess
import sys

import pytest
from conftest import ROOT

from bench import run


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_cpu_backend_is_refused():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig8-fleet.zipf",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_device_check_refuses_cpu_and_too_few_chips():
    with pytest.raises(run.NoChip, match="no TPU"):
        run.device_info(1)
    with pytest.raises(run.NoChip, match="asks for 4"):
        run.device_info(4, require_tpu=False)


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copytree(os.path.join(ROOT, "tests", "bench"),
                    tmp_path / "tests" / "bench")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "fig8-fleet.zipf",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
