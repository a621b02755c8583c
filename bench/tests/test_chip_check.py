"""A run needs a TPU whose peaks are known: anything else fails before a
result is printed."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import peaks  # noqa: E402
import run  # noqa: E402


@pytest.mark.parametrize("kind", ["TPU v5e", "TPU v4", "cpu", ""])
def test_a_device_kind_without_published_peaks_is_an_error(kind):
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for(kind)


def test_the_v5e_peaks_are_the_published_ones():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_a_cpu_platform_is_refused_in_process():
    with pytest.raises(run.NoChip, match="no TPU"):
        run.check_device(1)


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "pagerank.graph500-22",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_a_run_on_a_cpu_only_host_exits_nonzero_with_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    _no_result(proc)


def test_a_checkout_of_the_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    _no_result(proc)
