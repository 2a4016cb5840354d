"""The general traffic generator."""
import json

import numpy as np

import gen_traffic
from conftest import BENCH


def _traffic(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_cycle_is_the_paper_request_mix():
    from repro.serve.dse import paper_request_mix
    from repro.workloads.cnn import PAPER_WORKLOADS, cnn_workload
    from repro.workloads.pack import pack_workloads

    ws = pack_workloads([(n, cnn_workload(n)) for n in PAPER_WORKLOADS])
    mix = paper_request_mix(ws, 40)
    s = gen_traffic.Stream(_traffic("sweep"), 4, run_seed=5)
    for i, req in enumerate(mix):
        r = s[i]
        assert tuple(ws.names[j] for j in r.subset) == req.ws.names
        assert r.objective == req.objective


def test_same_seed_same_stream_and_large_seeds_work():
    t = _traffic("paper_open")
    big = 2 ** 31 + 12345
    a, b = gen_traffic.Stream(t, 4, big), gen_traffic.Stream(t, 4, big)
    assert a.take(2000) == b.take(2000)
    assert a.take(5) != gen_traffic.Stream(t, 4, big + 1).take(5)
    assert a.take(5) != gen_traffic.Stream(t, 4, big, warm=True).take(5)
    assert all(0 <= r.seed < 2 ** 31 for r in a.take(2000))


def test_every_seed_gets_the_same_gaps_in_another_order():
    t = dict(_traffic("paper_open"), rate_per_s=100.0)
    a = gen_traffic.arrival_offsets(t, 1, 10.0)
    b = gen_traffic.arrival_offsets(t, 2, 10.0)
    assert not np.array_equal(a, b)
    ga = np.sort(np.diff(np.concatenate([[0.0], a])))
    gb = np.sort(np.diff(np.concatenate([[0.0], b])))
    n = min(len(ga), len(gb))
    # the same quantiles of the exponential law, cut at the span's end
    assert abs(len(a) - len(b)) < 0.1 * n
    assert abs(np.mean(ga) - 1 / 100.0) < 0.2 / 100.0


def test_bursty_and_uniform_arrivals_keep_the_mean_rate():
    t = dict(_traffic("paper_open"), rate_per_s=200.0, arrivals="bursty",
             burst_size=16, burst_spread_s=0.001)
    a = gen_traffic.arrival_offsets(t, 7, 60.0)
    b = gen_traffic.arrival_offsets(t, 8, 60.0)
    assert abs(len(a) / 60.0 - 200.0) < 20.0
    gaps = np.diff(a)
    # within a burst the gaps are the spread; between bursts far longer
    assert np.mean(np.isclose(gaps, 0.001)) > 0.9
    assert not np.array_equal(a, b)
    u = gen_traffic.arrival_offsets(dict(t, arrivals="uniform"), 7, 10.0)
    assert np.allclose(np.diff(u), 1 / 200.0)


def test_budgets_cycle():
    t = dict(_traffic("sweep"), budgets=[{"pop_size": 8, "generations": 2},
                                         {"pop_size": 4, "generations": 1}])
    s = gen_traffic.Stream(t, 4, run_seed=2 ** 33)
    assert [(r.pop_size, r.generations) for r in s.take(4)] == \
        [(8, 2), (4, 1), (8, 2), (4, 1)]
