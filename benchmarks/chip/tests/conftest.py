"""Tests of the chip benchmark, run on the CPU:

    python -m pytest benchmarks/chip/tests

``tiny`` is a benchmark directory of its own with the real cells cut to a
size the CPU holds (P=16, G=3, 9 slots), which the harness runs without
looking for a chip.
"""
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402

TINY_SLOTS = {"paper_cnn4": 9, "paper_cnn4.mesh4": 18}


def make_tiny(dest: Path) -> harness.Paths:
    """A copy of the benchmark at P=16, G=3 with few slots and small
    reference blocks, and the CPU's "peaks" so the readers can run."""
    here = dest / "bench"
    for sub in ("configs", "traffic", "checks"):
        (here / sub).mkdir(parents=True, exist_ok=True)
    shutil.copytree(BENCH / "metrics", here / "metrics")
    for f in ("layer_metrics.py", "trace_reduce.py", "work_count.py"):
        shutil.copy(BENCH / f, here / f)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if not any(w["config"] == "paper_cnn4.mesh4" for w in bench["workloads"]):
        # the four-chip cell's files are kept for the PR that proves it on
        # the chip; the tests run it at the tiny size all the same
        bench["workloads"].append({"name": "cnn4.sweep.mesh4",
                                   "config": "paper_cnn4.mesh4",
                                   "traffic": "sweep", "chips": 4,
                                   "why": "test"})
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    for name, slots in TINY_SLOTS.items():
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        cfg["service"]["max_slots"] = slots
        (here / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name in ("sweep", "paper_open"):
        tr = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
        tr.update(pop_size=16, generations=3)
        if tr["loop"] == "open":
            tr.update(rate_per_s=40.0, lead_s=0.5)
        (here / "traffic" / f"{name}.json").write_text(json.dumps(tr))
    for w in bench["workloads"]:
        ch = json.loads((BENCH / "checks" / f"{w['name']}.json").read_text())
        ch.update(block=9, lanes=3, sample=0)
        (here / "checks" / f"{w['name']}.json").write_text(json.dumps(ch))
    peaks = json.loads((BENCH / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"name": "test host", "hbm_bytes_per_s": 1e10}
    (here / "peaks.json").write_text(json.dumps(peaks))
    (dest / "src").symlink_to(ROOT / "src")
    return harness.Paths(here=here, root=dest)


@pytest.fixture
def tiny(tmp_path) -> harness.Paths:
    return make_tiny(tmp_path)


def run_tiny(paths, workload, seconds=1.5, trace=False, seed=2 ** 31 + 17):
    import time

    return harness.run_cell(workload, seed, seconds, trace,
                            t_start=time.time(), paths=paths,
                            require_tpu=False, compile_cache=False,
                            log=lambda m: None)
