#!/usr/bin/env python3
"""Records a small trace on a TPU, for a test of the whole trace reading:

    python3 benchmarks/chip/tests/record_trace.py OUT_DIR

runs the tiny sweep cell (P=16, G=3, 9 slots; see ``conftest.make_tiny``)
traced for half a second and writes into OUT_DIR the trace
(``tiny_sweep.xplane.pb.gz``) and what the run read from it
(``tiny_sweep.json``: the device, the per-layer metrics, busy and window
seconds, and the span engine's launch log, request ids replaced by their
count).
"""
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import conftest  # noqa: E402
import harness  # noqa: E402

LOG_KEYS = ("t_dispatch", "dispatch_s", "harvest_s", "wait_s", "slots",
            "P", "G", "W")


def main(out: str) -> int:
    out_dir = Path(out)
    out_dir.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=harness.ROOT) as tmp:
        paths = conftest.make_tiny(Path(tmp))
        cell = harness.Cell("cnn4.sweep", paths=paths, compile_cache=False)
        cell.setup(2 ** 31 + 101, trace=True)
        w = cell.measure(2 ** 31 + 101, 0.5, trace=True, t_start=time.time())
        cell.close()
        metrics, device, _ = cell.per_layer(w, keep_trace=tmp)
        with open(Path(tmp) / "cnn4.sweep.xplane.pb", "rb") as f, \
                gzip.open(out_dir / "tiny_sweep.xplane.pb.gz", "wb") as g:
            shutil.copyfileobj(f, g)
    log = [dict({k: r[k] for k in LOG_KEYS if k in r}, n_reqs=len(r["reqs"]))
           for r in w["launch_log"]]
    res = {"device": dict(cell.dev, **device), "metrics": metrics,
           "window": [w["lo"], w["hi"]], "launch_log": log}
    (out_dir / "tiny_sweep.json").write_text(json.dumps(res, indent=1))
    print(json.dumps({k: res[k] for k in ("device", "metrics")}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
