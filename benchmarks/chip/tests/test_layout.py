"""The harness is driven by data: a new configuration, traffic mix, check
and per-layer metric are found from added files and entries alone."""
import json

from conftest import run_tiny


def test_new_cell_and_metric_from_added_files(tiny):
    here = tiny.here
    cfg = json.loads((here / "configs" / "paper_cnn4.json").read_text())
    cfg.update(name="paper_cnn4.small_area", area_mm2=120.0)
    (here / "configs" / "paper_cnn4.small_area.json").write_text(json.dumps(cfg))
    tr = json.loads((here / "traffic" / "paper_open.json").read_text())
    tr.update(rate_per_s=25.0, subsets=["singles"], objectives=["edp"])
    (here / "traffic" / "singles_edp.json").write_text(json.dumps(tr))
    ch = json.loads((here / "checks" / "cnn4.paper_open.json").read_text())
    (here / "checks" / "small.singles.json").write_text(json.dumps(ch))
    (here / "metrics" / "window.answered.py").write_text(
        '"""Requests of the window that were answered."""\n\n\n'
        'def read(run):\n'
        '    return sum(1 for r in run.records if r["t_done"] is not None)\n')
    bench = json.loads((tiny.root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "paper_cnn4.small_area",
                             "source": "test", "file": "x", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "small.singles",
                               "config": "paper_cnn4.small_area",
                               "traffic": "singles_edp", "chips": 1,
                               "why": "test"})
    bench["end_to_end"][1]["workloads"].append("small.singles")
    bench["per_layer"].append({"name": "window.answered", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "service (serve/dse.py)",
                               "moves": "latency_p95_s",
                               "workloads": ["small.singles"]})
    (tiny.root / "BENCHMARK.json").write_text(json.dumps(bench))

    out = run_tiny(tiny, "small.singles", trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["window.answered"]["value"] == out["attempted"] > 0
    # the open-loop cell's own per-layer metrics are not asked for here
    assert "service.queue_wait_p95_ms.open" not in out["metrics"]
    out = run_tiny(tiny, "small.singles")
    assert set(out["metrics"]) == {"latency_p95_s", "setup_s"}


def _add_cell(tiny, name, traffic):
    bench = json.loads((tiny.root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": "paper_cnn4",
                               "traffic": traffic, "chips": 1, "why": "test"})
    bench["end_to_end"][1]["workloads"].append(name)
    (tiny.root / "BENCHMARK.json").write_text(json.dumps(bench))
    ch = json.loads((tiny.here / "checks" / "cnn4.paper_open.json").read_text())
    (tiny.here / "checks" / f"{name}.json").write_text(json.dumps(ch))


def test_new_arrival_law_from_an_added_module(tiny):
    """A mix the general generator cannot express comes in as a module of
    its own: here arrivals in evenly spaced pairs, and a stream in which
    every third request repeats the one before it exactly."""
    (tiny.here / "traffic" / "pair_repeats.py").write_text('''"""Pairs of requests every 50 ms, off the window's edges; every third
request repeats the one before it."""
import numpy as np

import gen_traffic

TRAFFIC = {"loop": "open", "lead_s": 0.5, "pop_size": 16, "generations": 3,
           "subsets": ["singles", "pairs"], "objectives": ["edp", "e"]}


class Stream(gen_traffic.Stream):
    def __getitem__(self, i):
        return super().__getitem__(i - 1 if i % 3 == 2 else i)


def arrival_offsets(traffic, run_seed, span_s):
    t = np.repeat(np.arange(0.025, span_s, 0.05), 2)
    return t[t < span_s]
''')
    _add_cell(tiny, "cnn4.pair_repeats", "pair_repeats")
    out = run_tiny(tiny, "cnn4.pair_repeats", seconds=1.0)
    assert out["correct"], out["checks"]
    # 1.0 s of window holds 20 instants of two requests each
    assert out["attempted"] == 40 and out["failed"] == 0
    assert out["checks"]["answers_checked"]["value"] == 40


def test_mixed_budgets_and_bursts_from_data(tiny):
    """Two GA budgets in one queue and bursty arrivals, from parameters
    alone; the check runs the reference once per budget."""
    tr = json.loads((tiny.here / "traffic" / "paper_open.json").read_text())
    tr.pop("pop_size")
    tr.pop("generations")
    tr.update(budgets=[{"pop_size": 16, "generations": 3},
                       {"pop_size": 12, "generations": 2}],
              arrivals="bursty", rate_per_s=40.0, burst_size=8,
              burst_spread_s=0.002)
    (tiny.here / "traffic" / "mixed_bursts.json").write_text(json.dumps(tr))
    _add_cell(tiny, "cnn4.mixed_bursts", "mixed_bursts")
    out = run_tiny(tiny, "cnn4.mixed_bursts")
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 16 and out["failed"] == 0
    assert out["compiles"]["window"] == 0
