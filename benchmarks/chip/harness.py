"""One benchmark run of one cell: set up, measure a window, check, report.

Everything that belongs to one cell is found by name:
``BENCHMARK.json`` names the cell's configuration and traffic mix;
``configs/<config>.json`` holds the deployment, ``traffic/<traffic>.json``
(or ``.py``, see ``gen_traffic``) the request stream and its arrivals,
``checks/<cell>.json`` the limits of the output
check, ``metrics/<metric>.py`` each per-layer metric's reader, and
``peaks.json`` the device's published peaks.

A run (``run_cell``):

1. checks that JAX sees a TPU with the cell's chips (unless told not to);
2. sets up: the persistent compile cache inside the checkout, the
   service, and a warm-up of the cell's own program shapes from requests
   of their own; ``setup_s`` runs from process start to the window;
3. after a lead-in of traffic, measures a window of ``--seconds`` (a closed-loop backlog or open-loop
   arrivals, see ``gen_traffic``), counting compilations inside it;
4. reads the device's peak memory, frees the service, and checks the
   window's answers against the plain reference (``plain_ref``);
5. returns the result record; ``--trace 1`` traces the window (at most
   ``TRACE_S`` of it) and reports the per-layer metrics instead of the
   end-to-end ones.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
# an answer that has not come this long after the window closed never
# comes
LATE_S = 60.0
# a traced run traces at most this much of its window: the per-layer
# readings need a few seconds, and a trace of a long window of small
# launches takes minutes to read
TRACE_S = 10.0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class Paths:
    """Where a run finds its files: the benchmark directory and the
    checkout root (tests point these at a directory of their own)."""

    def __init__(self, here: Path = HERE, root: Path = ROOT):
        self.here, self.root = Path(here), Path(root)

    def benchmark(self) -> dict:
        return json.loads((self.root / "BENCHMARK.json").read_text())

    def config(self, name: str) -> dict:
        return json.loads((self.here / "configs" / f"{name}.json").read_text())

    def traffic(self, name: str) -> Tuple[dict, ModuleType]:
        """A traffic mix's parameters and its generator: the general one
        (``gen_traffic``) for ``traffic/<name>.json``, or the module
        ``traffic/<name>.py`` with its ``TRAFFIC`` parameters."""
        py = self.here / "traffic" / f"{name}.py"
        if py.exists():
            mod = self._module(py, "bench_traffic_" + name)
            return dict(mod.TRAFFIC), mod
        import gen_traffic

        return (json.loads((self.here / "traffic" / f"{name}.json").read_text()),
                gen_traffic)

    def checks(self, cell: str) -> dict:
        return json.loads((self.here / "checks" / f"{cell}.json").read_text())

    def peaks(self) -> dict:
        return json.loads((self.here / "peaks.json").read_text())

    def reader(self, metric: str) -> Callable:
        path = self.here / "metrics" / f"{metric}.py"
        return self._module(path, "bench_metric_" + metric).read

    def _module(self, path: Path, name: str) -> ModuleType:
        spec = importlib.util.spec_from_file_location(
            name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        sys.path.insert(0, str(self.here))
        try:
            spec.loader.exec_module(mod)
        finally:
            sys.path.remove(str(self.here))
        return mod


class CompileCounter:
    """Executables built since start, from ``jax.monitoring`` events:
    ``programs`` counts every backend compile request (a fresh XLA
    compile or a persistent-cache load), ``cache_hits`` the loads."""

    def __init__(self):
        import jax

        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.programs += 1

    def _on_event(self, event, **kw):
        if event == CACHE_HIT:
            self.cache_hits += 1


def peak_for(peaks: dict, kind: str) -> dict:
    """The published peaks of ``kind``; an unknown device is an error."""
    try:
        return peaks["devices"][kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"peaks.json (known: {sorted(peaks['devices'])})")


def quantile(values: List[float], q: float) -> float:
    """The q-quantile (0 < q < 1) by nearest rank: the smallest sample
    with at least a share q of the samples at or below it.  +inf entries
    (answers that never came) sort last."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(int(np.ceil(q * len(v))) - 1, 0)])


def span_engine_class():
    """A pipelined ``SearchEngine`` that puts host timers and
    ``TraceAnnotation``s around ``dispatch`` and ``harvest``, and times the
    wait for the launch's outputs apart inside ``harvest``.  Used by the
    traced run only."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.core.engine import SearchEngine

    class SpanEngine(SearchEngine):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.launch_log: List[dict] = []

        def dispatch(self, plan, **kw):
            t0 = time.perf_counter()
            with TraceAnnotation("bench.dispatch"):
                pend = super().dispatch(plan, **kw)
            rec = {"t_dispatch": t0, "dispatch_s": time.perf_counter() - t0,
                   "reqs": [id(r) for r in plan.requests],
                   "slots": plan.slots,
                   "W": sum(r.ws.n for r in plan.requests),
                   "P": plan.requests[0].pop_size,
                   "G": plan.requests[0].generations}
            self.launch_log.append(rec)
            pend.bench_rec = rec
            return pend

        def harvest(self, pending):
            t0 = time.perf_counter()
            with TraceAnnotation("bench.harvest"):
                with TraceAnnotation("bench.harvest_wait"):
                    jax.block_until_ready(
                        [x for x in (pending.thin, pending.ga, pending.pareto)
                         if x is not None])
                    wait = time.perf_counter() - t0
                out = super().harvest(pending)
            rec = getattr(pending, "bench_rec", None)
            if rec is not None:
                rec["harvest_s"] = time.perf_counter() - t0
                rec["wait_s"] = wait
            return out

    return SpanEngine


class RunData:
    """What a per-layer metric reader reads: the reduced trace, the launch
    log of the span engine, the harness's submit stamps, the window, the
    cell and the device's peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def launches_in_window(self) -> List[dict]:
        lo, hi = self.window
        return [r for r in self.launch_log
                if lo <= r["t_dispatch"] < hi and "harvest_s" in r]


# ------------------------------------------------------------------ windows
class ClosedLoop:
    """A backlog of ``outstanding`` requests: every completion submits the
    next one.  The window opens at the completion that ends launch
    ``lead`` and closes at the first launch end at least ``seconds``
    later, so it holds whole launches and all of their time.  (A launch
    end is taken as every ``slots``-th completion: exact while every
    launch is full, as with one budget; with several budgets in the
    backlog the ends fall between launches.)"""

    def __init__(self, svc, make_req, outstanding: int, slots: int,
                 lead: int, seconds: float):
        self.svc, self.make_req = svc, make_req
        self.outstanding, self.slots = outstanding, slots
        self.lead_done = lead * slots
        self.seconds = seconds
        self.lock = threading.Lock()
        self.next_i = 0
        self.done = 0
        self.running = True
        self.t_open = self.t_close = None
        self.opened, self.closed = threading.Event(), threading.Event()
        self.records: List[dict] = []  # per request: i, req, fut, t_submit, t_done
        self.done_at_open = self.done_at_close = None

    def _submit(self):
        with self.lock:
            i = self.next_i
            self.next_i += 1
            rec = {"i": i, "t_done": None, "error": None}
            self.records.append(rec)
        rec["req"] = req = self.make_req(i)
        rec["t_submit"] = time.perf_counter()
        fut = self.svc.submit(req)
        rec["fut"] = fut
        fut.add_done_callback(lambda f, rec=rec: self._on_done(rec, f))

    def _on_done(self, rec, fut):
        now = time.perf_counter()
        with self.lock:
            rec["t_done"] = now
            if fut.cancelled() or fut.exception() is not None:
                rec["error"] = "cancelled" if fut.cancelled() else repr(fut.exception())
            self.done += 1
            boundary = self.done % self.slots == 0
            if boundary and self.done == self.lead_done:
                self.t_open, self.done_at_open = now, self.done
                self.opened.set()
            elif (boundary and self.t_open is not None and self.t_close is None
                  and now - self.t_open >= self.seconds):
                self.t_close, self.done_at_close = now, self.done
                self.running = False
                self.closed.set()
            again = self.running
        if again:
            self._submit()

    def start(self):
        for _ in range(self.outstanding):
            self._submit()

    def in_window(self) -> List[dict]:
        lo, hi = self.t_open, self.t_close
        return [r for r in self.records
                if r["t_done"] is not None and lo < r["t_done"] <= hi]


class OpenLoop:
    """Requests due on a schedule from the generator, submitted from this
    thread whatever the service does; latency runs from each due time."""

    def __init__(self, svc, make_req, offsets: np.ndarray, lead_s: float,
                 seconds: float):
        self.svc, self.make_req = svc, make_req
        self.offsets, self.lead_s, self.seconds = offsets, lead_s, seconds
        self.records: List[dict] = []
        self.t0 = self.t_open = self.t_close = None

    def run(self, on_open: Callable[[float], None],
            on_close: Callable[[], None]):
        self.t0 = time.perf_counter()
        self.t_open = self.t0 + self.lead_s
        self.t_close = self.t_open + self.seconds
        opened = False
        for i, off in enumerate(self.offsets):
            due = self.t0 + float(off)
            if due >= self.t_close:
                break
            if not opened and due >= self.t_open:
                self._sleep_until(self.t_open)
                on_open(self.t_open)
                opened = True
            self._sleep_until(due)
            req = self.make_req(i)
            rec = {"i": i, "req": req, "t_due": due,
                   "t_submit": time.perf_counter(), "t_done": None,
                   "error": None}
            self.records.append(rec)
            fut = self.svc.submit(req)
            rec["fut"] = fut
            fut.add_done_callback(lambda f, rec=rec: self._on_done(rec, f))
        if not opened:
            self._sleep_until(self.t_open)
            on_open(self.t_open)
        self._sleep_until(self.t_close)
        on_close()

    @staticmethod
    def _on_done(rec, fut):
        rec["t_done"] = time.perf_counter()
        if fut.cancelled() or fut.exception() is not None:
            rec["error"] = "cancelled" if fut.cancelled() else repr(fut.exception())

    @staticmethod
    def _sleep_until(t):
        while True:
            d = t - time.perf_counter()
            if d <= 0:
                return
            time.sleep(min(d, 0.05))

    def in_window(self) -> List[dict]:
        return [r for r in self.records
                if self.t_open <= r["t_due"] < self.t_close]


# --------------------------------------------------------------------- run
def device_record(jax, n: int) -> dict:
    devs = jax.devices()[:n]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices())}


class Cell:
    """One cell's deployment, set up once; windows run on it in turn."""

    def __init__(self, workload: str, *, paths: Paths = Paths(),
                 require_tpu: bool = True, compile_cache: bool = True,
                 log=print):
        self.paths, self.log, self.workload = paths, log, workload
        self.bench = paths.benchmark()
        cell = next((w for w in self.bench["workloads"]
                     if w["name"] == workload), None)
        if cell is None:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        self.cell = cell
        self.config = paths.config(cell["config"])
        self.traffic, self.gen = paths.traffic(cell["traffic"])
        self.checks = paths.checks(workload)
        self.chips = int(cell["chips"])
        os.environ["REPRO_GRID_DENSITY"] = str(int(self.config["grid_density"]))

        import jax

        self.jax = jax
        self.dev = device_record(jax, self.chips)
        log(f"devices: platform={self.dev['platform']} "
            f"device_kind={self.dev['kind']} count={self.dev['count']}")
        if require_tpu and (self.dev["platform"] != "tpu"
                            or self.dev["count"] < self.chips):
            raise NoChip(f"the cell needs {self.chips} TPU chip(s); JAX "
                         f"finds {self.dev['count']} {self.dev['platform']} "
                         "device(s)")
        if compile_cache:
            # a fixed directory inside the checkout: every run of a cell
            # after its first finds all of its programs there
            jax.config.update("jax_compilation_cache_dir",
                              str(paths.root / ".jax_cache"))
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        self.counter = CompileCounter()
        src = str(paths.root / "src")
        if src not in sys.path:
            sys.path.insert(0, src)

    # ------------------------------------------------------------- set-up
    def setup(self, seed: int, trace: bool = False) -> None:
        """Build the service and run the warm-up: two full launches of the
        cell's own requests (from a stream of their own), pipelined as the
        window runs them, and for an open loop one partial launch of each
        narrower workload width, so every program the window uses is built
        or loaded here."""
        from repro.core import space
        from repro.core.engine import SearchEngine, SearchRequest
        from repro.serve.dse import AsyncDSEService
        from repro.workloads.pack import pack_workloads

        cfg = self.config
        if space.GRID_DENSITY != int(cfg["grid_density"]):
            space.configure_grid(int(cfg["grid_density"]))
        self.names = list(cfg["workloads"])
        ws = pack_workloads([(n, cfg["workloads"][n]) for n in self.names])
        subsets: Dict[tuple, object] = {}

        def to_request(r) -> SearchRequest:
            sub = subsets.get(r.subset)
            if sub is None:
                sub = subsets[r.subset] = ws.subset(list(r.subset))
            return SearchRequest(ws=sub, objective=r.objective,
                                 area_constr=float(cfg["area_mm2"]),
                                 seed=r.seed, backend=cfg["backend"],
                                 pop_size=r.pop_size,
                                 generations=r.generations,
                                 top_k=int(cfg["top_k"]))

        self.to_request = to_request
        svc_cfg = cfg["service"]
        self.slots = int(svc_cfg["max_slots"])
        mesh = None
        if cfg["layout"]["mesh"]:
            from repro.launch.mesh import make_search_mesh

            m = cfg["layout"]["mesh"]
            mesh = make_search_mesh(int(m["search"]), int(m["data"]))
        engine_cls = span_engine_class() if trace else SearchEngine
        self.engine = engine_cls(mesh=mesh, max_slots=self.slots,
                                 pipelined=bool(svc_cfg["pipelined"]))
        self.svc = AsyncDSEService(engine=self.engine,
                                   policy=svc_cfg["policy"],
                                   pipelined=bool(svc_cfg["pipelined"]))
        # per budget (P, G): two full launches, pipelined as the window
        # runs them; where launches can be partial (an open loop, or a
        # queue of several budgets), one more launch for each narrower
        # workload width, since a partial plan's workload axis is padded
        # only to its own widest subset
        warm = self.gen.Stream(self.traffic, len(self.names), seed, warm=True)
        first = warm.take(64 * self.slots)
        groups: Dict[tuple, list] = {}
        for r in first:
            groups.setdefault((r.pop_size, r.generations), []).append(r)
        partial = self.traffic["loop"] == "open" or len(groups) > 1
        for reqs in groups.values():
            full = reqs[:2 * self.slots]
            for f in self.svc.submit_all([to_request(r) for r in full]):
                f.result()
            if partial:
                for wd in sorted({len(r.subset) for r in full})[:-1]:
                    r = next(r for r in full if len(r.subset) == wd)
                    self.svc.submit(to_request(r)).result()

    # ------------------------------------------------------------ window
    def measure(self, seed: int, seconds: float, *, trace: bool = False,
                t_start: Optional[float] = None,
                traffic: Optional[dict] = None) -> dict:
        """One window of the cell's traffic (or ``traffic``, for a rate
        sweep) with request seeds from ``seed``.  ``setup_s`` runs from
        ``t_start`` (``time.time()`` at process start) to the start of the
        window.  Returns what the window left: its loop, bounds, compile
        count and the span engine's launch log."""
        jax = self.jax
        traffic = traffic or self.traffic
        stream = self.gen.Stream(traffic, len(self.names), seed)
        trace_dir = self.paths.root / ".bench_trace" / self.workload
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
        w = {"stream": stream, "trace_dir": trace_dir}
        span = []

        if trace:
            # the profiler starts before the lead-in, so its start-up
            # delays no request of the window
            jax.profiler.start_trace(str(trace_dir))

        def on_open(t_open):
            # set-up runs to the start of the window (perf_counter t_open)
            if t_start is not None:
                w["setup_s"] = (time.time() - t_start
                                - (time.perf_counter() - t_open))
            w["compiles_before"] = self.counter.programs
            if trace:
                span.append(jax.profiler.TraceAnnotation("bench.window"))
                span[0].__enter__()

        def on_close():
            w["compiles_after"] = self.counter.programs
            if trace:
                span[0].__exit__(None, None, None)
                jax.profiler.stop_trace()

        make_req = lambda i: self.to_request(stream[i])  # noqa: E731
        log_len = len(getattr(self.engine, "launch_log", []))
        if traffic["loop"] == "closed":
            loop = ClosedLoop(self.svc, make_req,
                              int(traffic["outstanding_launches"]) * self.slots,
                              self.slots, int(traffic["lead_launches"]),
                              seconds)
            loop.start()
            if not loop.opened.wait(300):
                raise RuntimeError("the closed loop never reached its window")
            on_open(loop.t_open)
            if not loop.closed.wait(seconds + 300):
                raise RuntimeError("the closed loop never closed its window")
            on_close()
        else:
            lead = float(traffic["lead_s"])
            loop = OpenLoop(self.svc, make_req,
                            self.gen.arrival_offsets(traffic, seed,
                                                     lead + seconds),
                            lead, seconds)
            loop.run(on_open, on_close)
        # every request resolves, a minute past the close at most
        deadline = time.perf_counter() + LATE_S
        for rec in loop.records:
            fut = rec.get("fut")
            if fut is None:
                continue
            try:
                fut.result(timeout=max(deadline - time.perf_counter(), 0.001))
            except Exception:  # noqa: BLE001 — a failed answer, counted below
                pass
        w.update(loop=loop, closed=traffic["loop"] == "closed",
                 lo=loop.t_open, hi=loop.t_close,
                 compiles=w["compiles_after"] - w["compiles_before"],
                 launch_log=list(getattr(self.engine, "launch_log", []))[log_len:])
        return w

    def close(self) -> int:
        """Stop the service, read the fullest chip's peak memory, and drop
        the program's state; returns the peak."""
        self.svc.close(timeout=LATE_S)
        devs = self.jax.devices()[:self.chips]
        mem = [d.memory_stats() or {} for d in devs]
        self.svc = self.engine = None
        gc.collect()
        return max(int(m.get("peak_bytes_in_use", 0)) for m in mem)

    # ------------------------------------------------------------ results
    @staticmethod
    def end_to_end(w: dict) -> Dict[str, float]:
        loop = w["loop"]
        window = loop.in_window()
        out: Dict[str, float] = {}
        if w["closed"]:
            work = sum(r["req"].pop_size * (r["req"].generations + 1)
                       for r in window if not r["error"])
            out["designs_per_s"] = work / (w["hi"] - w["lo"])
        else:
            lat = [(r["t_done"] - r["t_due"])
                   if (r["t_done"] is not None and not r["error"])
                   else float("inf") for r in window]
            out["latency_p50_s"] = quantile(lat, 0.5)
            out["latency_p95_s"] = quantile(lat, 0.95)
            lo, hi, recs = w["lo"], w["hi"], w["loop"].records
            out["completed_per_s"] = sum(
                1 for r in recs
                if r["t_done"] is not None and lo <= r["t_done"] < hi) / (hi - lo)
            out["offered_per_s"] = len(window) / (hi - lo)

            def backlog(t):
                return sum(1 for r in recs if r["t_submit"] < t
                           and (r["t_done"] is None or r["t_done"] >= t))

            out["backlog_open"] = backlog(lo)
            out["backlog_close"] = backlog(hi)
            out["generator_late_p95_s"] = quantile(
                [r["t_submit"] - r["t_due"] for r in window], 0.95)
        if w.get("setup_s") is not None:
            out["setup_s"] = w["setup_s"]
        return out

    def sample(self, w: dict, seed: int) -> List[dict]:
        """The answers to check: a sample of ``checks.sample`` drawn from
        the seed among those the window completed, or all of them."""
        window = w["loop"].in_window()
        answered = [r for r in window
                    if r["t_done"] is not None and not r["error"]]
        n = int(self.checks["sample"]) or len(answered)
        n = min(n, len(answered))
        if not answered:
            return []
        pick = np.random.default_rng([seed % 2 ** 63, 3]).choice(
            len(answered), size=n, replace=False)
        return [answered[i] for i in np.sort(pick)]

    def check(self, w: dict, seed: int, control: Optional[str] = None
              ) -> Dict[str, dict]:
        """Every number compared, each with its limit.  ``control`` (a
        dtype name) puts the reference computed in that precision in the
        program's place: its answers are compared instead."""
        loop = w["loop"]
        window = loop.in_window()
        failed = [r for r in window if r["t_done"] is None or r["error"]]
        numbers = {"missing": {"value": len(failed), "limit": 0},
                   "compiles_in_window": {"value": w["compiles"], "limit": 0}}
        sample = self.sample(w, seed)
        if not sample:
            numbers["answers_checked"] = {"value": 0, "limit": 1}
            return numbers
        stream = w["stream"]
        # the reference runs one (P, G) budget at a time
        groups: Dict[tuple, list] = {}
        for r in sample:
            groups.setdefault((int(r["req"].pop_size),
                               int(r["req"].generations)), []).append(r)
        t0 = time.perf_counter()
        gaps = {"score_gap": 0.0, "trajectory_miss": 0.0}
        for (P, G), part in groups.items():
            g = self._check_budget(part, stream, P, G, control)
            gaps["score_gap"] = max(gaps["score_gap"], g["score_gap"])
            gaps["trajectory_miss"] += g["trajectory_miss"] * len(part) / len(sample)
        self.log(f"reference: {len(sample)} answers checked in "
                 f"{time.perf_counter() - t0:.1f} s")
        numbers["answers_checked"] = {"value": len(sample),
                                      "limit": len(sample)}
        for k, v in gaps.items():
            numbers[k] = {"value": v, "limit": float(self.checks["limits"][k])}
        return numbers

    def _check_budget(self, part: List[dict], stream, P: int, G: int,
                      control: Optional[str]) -> Dict[str, float]:
        """``plain_ref.compare`` over the sampled answers of one budget."""
        import jax.numpy as jnp

        import plain_ref

        reqs = plain_ref.Requests(
            seeds=[r["req"].seed for r in part],
            subsets=[stream[r["i"]].subset for r in part],
            objectives=[r["req"].objective for r in part],
            areas=[r["req"].area_constr for r in part])
        kw = dict(pop_size=P, generations=G,
                  top_k=int(self.config["top_k"]),
                  block=int(self.checks["block"]),
                  lanes=int(self.checks["lanes"]))
        if control is None:
            res = [r["fut"].result() for r in part]
            got = {"top_scores": [x.top_scores for x in res],
                   "top_genomes": [x.top_genomes for x in res],
                   "convergence": [x.convergence for x in res]}
        else:
            c = plain_ref.answers(self.config, reqs,
                                  dtype=getattr(jnp, control), **kw)
            k = c.n_kept
            got = {"top_scores": [c.top_scores[i][:k[i]] for i in range(len(k))],
                   "top_genomes": [c.top_genomes[i][:k[i]] for i in range(len(k))],
                   "convergence": list(c.convergence)}
        ref = plain_ref.answers(self.config, reqs, **kw)
        rescored = plain_ref.rescore(self.config, reqs, got["top_genomes"])
        return plain_ref.compare(self.config, reqs, got, ref, rescored, G)

    @staticmethod
    def correct(numbers: Dict[str, dict]) -> bool:
        return all((n["value"] >= n["limit"]) if k == "answers_checked"
                   else (n["value"] <= n["limit"])
                   for k, n in numbers.items())

    def per_layer(self, w: dict, keep_trace: Optional[str] = None
                  ) -> tuple:
        """The traced window's per-layer metrics (those the benchmark lists
        for this cell and whose reader finds something), the device's busy
        and window seconds, and the breakdown."""
        import trace_reduce

        xp = trace_reduce.find_xplane(str(w["trace_dir"]))
        red = trace_reduce.reduce_file(xp)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xp, os.path.join(keep_trace,
                                         f"{self.workload}.xplane.pb"))
        shutil.rmtree(w["trace_dir"], ignore_errors=True)
        data = RunData(trace=red, launch_log=w["launch_log"],
                       window=(w["lo"], w["hi"]),
                       records=w["loop"].in_window(), cell=self.cell,
                       config=self.config, traffic=self.traffic,
                       peaks=peak_for(self.paths.peaks(), self.dev["kind"]))
        moves = {m["name"] for m in self.e2e_listed()}
        metrics = {}
        for m in self.bench["per_layer"]:
            listed = m.get("workloads")
            wanted = (self.workload in listed) if listed \
                else (m["moves"] in moves)
            if not wanted:
                continue
            v = self.paths.reader(m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device = {"busy_s": trace_reduce.busy_s(red),
                  "window_s": trace_reduce.window_s(red)}
        breakdown = {"device_ops": trace_reduce.top_ops(red),
                     "idle_gaps": trace_reduce.idle_gaps(red)}
        return metrics, device, breakdown

    def e2e_listed(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.workload in m.get("workloads", [self.workload])]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, paths: Paths = Paths(), require_tpu: bool = True,
             compile_cache: bool = True, keep_trace: Optional[str] = None,
             log=print) -> dict:
    """One run of the benchmark; returns the last line's object.
    ``require_tpu=False`` (tests) skips the look for a chip."""
    cell = Cell(workload, paths=paths, require_tpu=require_tpu,
                compile_cache=compile_cache, log=log)
    cell.setup(seed, trace=trace)
    if trace:
        seconds = min(seconds, TRACE_S)
    w = cell.measure(seed, seconds, trace=trace, t_start=t_start)
    memory_peak = cell.close()
    e2e = cell.end_to_end(w)
    numbers = cell.check(w, seed)
    window = w["loop"].in_window()
    out = {"correct": cell.correct(numbers), "attempted": len(window),
           "failed": sum(1 for r in window
                         if r["t_done"] is None or r["error"]),
           "metrics": {}, "device": dict(cell.dev)}
    out["device"]["memory_peak_bytes"] = memory_peak
    if trace:
        metrics, device, breakdown = cell.per_layer(w, keep_trace)
        out["metrics"] = metrics
        out["device"].update(device)
        out["breakdown"] = breakdown
    else:
        for m in cell.e2e_listed():
            if m["name"] in e2e:
                out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                             "unit": m["unit"]}
    out["window"] = {k: v for k, v in e2e.items() if k not in out["metrics"]}
    out["window"]["seconds"] = w["hi"] - w["lo"]
    out["compiles"] = {"window": w["compiles"],
                       "total": cell.counter.programs,
                       "cache_hits": cell.counter.cache_hits}
    out["checks"] = numbers
    return out
