"""Benchmark entry point — one bench per paper table/figure + kernels.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Writes machine-readable results under experiments/ and prints a summary.
``experiments/search_throughput.json`` is the repo's tracked perf
trajectory (designs-evaluated/sec + end-to-end search wall time).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

EXP = Path(__file__).resolve().parents[1] / "experiments"


def exp_dir() -> Path:
    """The experiments/ output dir, created on demand.  Shared by every
    bench's ``__main__`` block so they can be run directly."""
    EXP.mkdir(exist_ok=True)
    return EXP


def prepare_search_mesh(spec: str):
    """``--mesh`` argument (``'auto'`` or ``'SEARCHxPOP'``) -> 2-D search
    mesh, shared by the bench entry points.  CPU-only hosts expose one
    device, so this first fakes 8 XLA host devices — it must therefore run
    before anything initializes a jax backend (the benches keep their
    repro imports lazy for exactly this reason)."""
    import os

    if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip()
    from repro.launch.mesh import make_search_mesh

    if spec == "auto":
        return make_search_mesh()
    s, p = (int(v) for v in spec.lower().split("x"))
    return make_search_mesh(s, p)


# named rows kept alongside the top-level (dense, unsharded) trajectory
EXTRA_ROWS = ("sharded", "table", "service", "cache", "fused", "pipelined",
              "pareto")


def write_search_throughput(res: dict, *, row: str = None) -> Path:
    """Write ``experiments/search_throughput.json``.  ``row=None`` replaces
    the top-level (dense jnp, unsharded) trajectory; ``row="sharded"`` /
    ``row="table"`` updates that named row in place — every entry point
    (benchmarks.run, bench_search_throughput --mesh / --backend) keeps the
    other rows intact."""
    path = exp_dir() / "search_throughput.json"
    prior = json.loads(path.read_text()) if path.exists() else {}
    if row is None:
        out = res
        for r in EXTRA_ROWS:
            if r in prior:
                out[r] = prior[r]
    else:
        assert row in EXTRA_ROWS, row
        out = prior
        out[row] = res
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="1 seed instead of 5")
    args = ap.parse_args(argv)
    exp_dir()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_dse_service,
        bench_generalization,
        bench_joint_vs_separate,
        bench_kernels,
        bench_search_throughput,
        bench_throughput,
    )

    t0 = time.time()
    print("== kernels (parity) ==")
    kern = bench_kernels.run()
    with open(EXP / "kernels.json", "w") as f:
        json.dump(kern, f, indent=1)

    print("\n== throughput (paper Sec. IV: 36 s/design) ==")
    thru = bench_throughput.run()
    with open(EXP / "throughput.json", "w") as f:
        json.dump(thru, f, indent=1)

    print("\n== search throughput (batched one-jit stack; tracked trajectory) ==")
    sthru = bench_search_throughput.run(quick=args.quick)
    write_search_throughput(sthru)

    print("\n== search throughput (factorized table backend) ==")
    sthru_t = bench_search_throughput.run(quick=args.quick, backend="table")
    write_search_throughput(sthru_t, row="table")

    print("\n== search throughput (fused gen step + direct seed, grid sweep) ==")
    sthru_f = bench_search_throughput.run_fused(
        quick=args.quick, densities=(1, 2) if args.quick else (1, 2, 3))
    write_search_throughput(sthru_f, row="fused")

    print("\n== search throughput (pipelined transfer-thin engine) ==")
    sthru_p = bench_search_throughput.run_pipelined(quick=args.quick)
    write_search_throughput(sthru_p, row="pipelined")

    print("\n== DSE service (continuous batching of mixed requests) ==")
    svc = bench_dse_service.run(quick=args.quick)
    write_search_throughput(svc, row="service")

    print("\n== Fig. 2: joint vs separate ==")
    fig2 = bench_joint_vs_separate.run(seeds=1 if args.quick else 5)
    with open(EXP / "fig2_joint_vs_separate.json", "w") as f:
        json.dump(fig2, f, indent=1)

    print("\n== Fig. 3: generalization loss across objectives ==")
    fig3 = bench_generalization.run()
    with open(EXP / "fig3_generalization.json", "w") as f:
        json.dump(fig3, f, indent=1)

    print(f"\nall benches done in {time.time()-t0:.0f}s; results in {EXP}/")
    return 0


if __name__ == "__main__":
    sys.exit(main())
