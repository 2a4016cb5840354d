#!/usr/bin/env bash
# Tier-1 gate + benchmark smoke, as run by .github/workflows/ci.yml:
#   bash tools/ci.sh               # tier-1 on the host's real device set
#   bash tools/ci.sh multidevice   # tier-1 + sharding tests + sharded bench
#                                  # row on a fake 8-device host
#   bash tools/ci.sh bench-smoke   # tiny search-throughput run per backend;
#                                  # appends the 'table', 'service',
#                                  # 'fused' and 'pipelined' rows of
#                                  # experiments/search_throughput.json
#   bash tools/ci.sh serve-smoke   # DSE-service smoke, three legs: sync
#                                  # fifo (~32 mixed requests, all results
#                                  # finite), sync EDF (launch order ==
#                                  # earliest-absolute-deadline-first on a
#                                  # mixed-deadline paper_request_mix) and
#                                  # async priority (mixed-priority mix
#                                  # through AsyncDSEService, futures all
#                                  # finite) — plus the virtual-clock
#                                  # scheduler-sim suite
#   bash tools/ci.sh fault-smoke   # anytime fault-tolerance gate: the
#                                  # segmented-GA parity + checkpoint/
#                                  # resume suite, the fault-injection
#                                  # sim suite (retry/backoff/quarantine/
#                                  # partials on the virtual clock), and
#                                  # the retry lane recovering injected
#                                  # chunk faults over the REAL engine
#   bash tools/ci.sh cache-smoke   # result-cache gate: the request_key /
#                                  # plan_key / LRU / disk-tier /
#                                  # streaming test suite, then an
#                                  # identical paper mix resubmitted
#                                  # through a cache-armed service (sync
#                                  # + async + PIPELINED thin-result
#                                  # engine) — zero new GA launches,
#                                  # bit-identical results; records the
#                                  # 'cache' row
#   bash tools/ci.sh pareto-smoke  # Pareto-front gate: the NSGA-II
#                                  # numpy-oracle parity suite
#                                  # (tests/test_pareto.py) and a quick
#                                  # pareto bench recording the 'pareto'
#                                  # row of search_throughput.json
#
# The scheduler-sim suite (tests/test_scheduler_sim.py) is part of the
# plain pytest run, so it executes in BOTH the tier-1 (1-device) and
# multidevice (fake-8-device) jobs — the harness is device-count-free.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" == "multidevice" ]]; then
  # fake 8 XLA host devices so the @pytest.mark.multidevice sharding tests
  # (tests/test_search_sharded.py, tests/test_engine.py) actually exercise
  # the 2-D mesh on CPU CI
  export XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}"
  python -m pytest -x -q
  python -m benchmarks.bench_search_throughput --quick --mesh 2x4
elif [[ "${1:-}" == "bench-smoke" ]]; then
  python -m benchmarks.bench_search_throughput --quick
  python -m benchmarks.bench_search_throughput --quick --backend table
  python -m benchmarks.bench_search_throughput --quick --fused --grid-density 1,2
  python -m benchmarks.bench_search_throughput --quick --pipelined
  python -m benchmarks.bench_search_throughput --quick --pareto
  python -m benchmarks.bench_dse_service --quick
elif [[ "${1:-}" == "serve-smoke" ]]; then
  python -m pytest -x -q tests/test_scheduler_sim.py
  python -m benchmarks.bench_dse_service --smoke
elif [[ "${1:-}" == "fault-smoke" ]]; then
  python -m pytest -x -q tests/test_fault_sim.py tests/test_ga_segments.py
  python -m benchmarks.bench_dse_service --fault-smoke
elif [[ "${1:-}" == "cache-smoke" ]]; then
  python -m pytest -x -q tests/test_result_cache.py
  python -m benchmarks.bench_dse_service --cache-smoke
  python -m benchmarks.bench_dse_service --cache --quick
elif [[ "${1:-}" == "pareto-smoke" ]]; then
  python -m pytest -x -q tests/test_pareto.py
  python -m benchmarks.bench_search_throughput --quick --pareto
else
  python -m pytest -x -q
  python -m benchmarks.run --quick
fi
