#!/usr/bin/env python3
"""The chip benchmark of the DSE service: one run of one cell.

    python3 benchmarks/chip/run.py --workload cnn4.sweep --seed 7 \
        --seconds 30 --trace 0

Run from the checkout root, one process, on a machine whose TPU chips it
may hold alone.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, and
last ``checks``: every number compared with its limit, which also ends
standard error.  Exit codes: 0 a result was printed; 3 JAX finds no TPU or
fewer chips than the cell asks for (nothing printed); 1 any other failure
(nothing printed).
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced window's .xplane.pb to this "
                         "directory")
    args = ap.parse_args(argv)

    import harness

    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               keep_trace=args.keep_trace, log=log)
    except harness.NoChip as e:
        log(f"no result: {e}")
        return 3
    except Exception:  # noqa: BLE001 — the run failed: say why, print nothing
        traceback.print_exc()
        log("no result: the run failed")
        return 1
    log(f"correct={out['correct']}")
    for name, n in out["checks"].items():
        log(f"check {name}: {n['value']!r} (limit {n['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
