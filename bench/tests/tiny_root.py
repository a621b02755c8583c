"""A benchmark root at test size: the repo's BENCHMARK.json with each
configuration's scale cut, so a whole run takes seconds on the CPU."""

import json
import os
import sys
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402

TEST_PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
SCALES = {"graph500-22": 10, "graph500-20": 11}


def make_root(tmp: Path) -> Path:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True, exist_ok=True)
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["scale"] = SCALES[c["name"]]
        (tmp / c["file"]).write_text(json.dumps(cfg))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run_tiny(root: Path, workload: str, seed: int = 5, trace: int = 0):
    """One whole run of ``workload`` on the CPU, past the look for a chip."""

    args = SimpleNamespace(workload=workload, seed=seed, seconds=0.0,
                           trace=trace)
    return run.run_cell(args, root=root,
                        device_check=lambda chips: TEST_PEAKS)


os.environ.setdefault("JAX_PLATFORMS", "cpu")
