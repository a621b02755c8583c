"""The control, the plain reference computed one precision lower (bfloat16
for the configurations' float32), fails the limits that sound runs of the
program pass."""

import json

import pytest

from tiny_root import SCALES, ROOT, run

CELLS = ["pagerank.graph500-22", "ppr-rowtable.graph500-20"]


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
@pytest.mark.parametrize("cell", CELLS)
def test_the_bfloat16_control_fails_a_limit(cell, seed):
    parts = run.resolve(ROOT, run.load_spec(ROOT), cell)
    cfg = dict(parts.cfg, scale=SCALES[parts.cell["config"]])
    job, traffic = parts.job, parts.traffic
    inputs = job.build(cfg, traffic, seed)
    want = job.reference(job.host_inputs(inputs), traffic)
    numbers = job.compare(job.control(inputs, traffic),
                          traffic["iterations"], want, traffic)
    failing = {k for k, v in numbers.items() if v > job.LIMITS[k]}
    assert "rank_max_rel_err" in failing, json.dumps(numbers)
    # float32 through the same control stays well inside the limit
    import jax.numpy as jnp

    numbers32 = job.compare(job.control(inputs, traffic, dtype=jnp.float32),
                            traffic["iterations"], want, traffic)
    assert all(v <= job.LIMITS[k] for k, v in numbers32.items()), numbers32


@pytest.mark.parametrize("cell", CELLS)
def test_control_script_gives_the_verdicts_run_gives(tmp_path, cell):
    import control
    from tiny_root import make_root

    root = make_root(tmp_path)
    parts = run.resolve(root, run.load_spec(root), cell)
    out = control.readings(parts, 7, program=True)
    assert out["program"]["correct"], json.dumps(out)
    assert not out["control"]["correct"], json.dumps(out)
    assert set(out["control"]["checks"]) <= set(parts.job.LIMITS)
