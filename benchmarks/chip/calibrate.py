#!/usr/bin/env python3
"""Readings that set the benchmark's limits and rates; not part of a run.

    python3 benchmarks/chip/calibrate.py --workload cnn4.sweep \
        --seeds 101,102,103 --seconds 5 --control bfloat16 --out FILE
    python3 benchmarks/chip/calibrate.py --workload cnn4.paper_open \
        --seeds 7 --seconds 5 --rates 200,400,800 --out FILE

One process sets the cell up once, then per seed measures a short window
at the cell's own load and prints (and appends to ``--out``) one JSON line:
the numbers the output check compares for the program (the lower
readings of its limits) and, with ``--control``, for the plain reference
computed in that lower precision and put in the program's place (the
upper readings).  ``--rates`` instead runs one open-loop window per
offered rate and reports what the service completed, to find the highest
rate it sustains.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the control on the first N seeds only")
    ap.add_argument("--rates", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import harness

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")

    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.Cell(args.workload,
                        log=lambda m: print(f"[cal] {m}", file=sys.stderr))
    cell.setup(seeds[0])
    emit({"workload": args.workload, "setup_s": time.time() - T_START,
          "compiles": cell.counter.programs,
          "cache_hits": cell.counter.cache_hits})
    if args.rates:
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = dict(cell.traffic, rate_per_s=rate)
            w = cell.measure(seeds[0], args.seconds, traffic=traffic)
            e2e = cell.end_to_end(w)
            emit({"rate_per_s": rate, **e2e, "compiles": w["compiles"],
                  "due": len(w["loop"].in_window())})
        return 0
    windows = []
    for seed in seeds:
        w = cell.measure(seed, args.seconds)
        windows.append((seed, w))
        emit({"seed": seed, "phase": "window", **cell.end_to_end(w)})
    peak = cell.close()
    emit({"memory_peak_bytes": peak})
    n_control = len(windows) if args.control_seeds is None else args.control_seeds
    for j, (seed, w) in enumerate(windows):
        rec = {"seed": seed, "program": cell.check(w, seed)}
        if args.control and j < n_control:
            rec["control"] = cell.check(w, seed, control=args.control)
        emit(rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
