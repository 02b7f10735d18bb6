"""The cell runner: build a cell's sessions, warm up, serve the measured
window through ``JoinService``, check the results against the plain
references, and compute the metrics.

Everything that belongs to one configuration, traffic mix or metric is
found by name: ``BENCHMARK.json`` names the cell, ``bench/configs/<config>``
(``.json`` sizes, ``.py`` generator) the configuration,
``bench/traffic/<traffic>.json`` the mix, and ``bench/metrics/<metric>.py``
each metric's reader.  The harness itself knows two ways to submit a
session (``"pairs"`` through ``JoinService.submit``, ``"embeddings"``
through ``JoinService.submit_embeddings`` on a dense or blocked machine
phase) and one crowd (perfect)."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
import traceback
from typing import List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration, generator,
    traffic and metric definitions resolved from their files."""

    name: str
    chips: int
    config: dict
    generator: object
    traffic: dict
    metrics: List[dict]   # metric entries of BENCHMARK.json this cell reports
    bench_dir: str = BENCH

    @classmethod
    def find(cls, name: str, bench_dir: str = BENCH,
             benchmark: Optional[dict] = None) -> "Cell":
        root = os.path.dirname(bench_dir)
        benchmark = benchmark or load_json(os.path.join(root,
                                                        "BENCHMARK.json"))
        cells = {w["name"]: w for w in benchmark["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        w = cells[name]
        conf = {c["name"]: c for c in benchmark["configs"]}[w["config"]]
        base = os.path.join(root, os.path.splitext(conf["file"])[0])
        metrics = []
        for group in ("end_to_end", "per_layer"):
            for m in benchmark[group]:
                if name in m.get("workloads", [name]):
                    metrics.append({**m, "group": group})
        return cls(
            name=name, chips=int(w["chips"]),
            config=load_json(os.path.join(root, conf["file"])),
            generator=load_module(base + ".py", "bench_config_"
                                  + w["config"]),
            traffic=load_json(os.path.join(bench_dir, "traffic",
                                           w["traffic"] + ".json")),
            metrics=metrics, bench_dir=bench_dir)

    def reader(self, metric: str):
        """The module ``metrics/<metric>.py``; its ``read(record)`` returns
        the metric's value, or None where the run has nothing to read."""
        return load_module(os.path.join(self.bench_dir, "metrics",
                                        metric + ".py"),
                           "bench_metric_" + metric.replace(".", "_"))


# ---------------------------------------------------------------------------
# spans, counters and compile events of one run
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    """One session served in the window, with what the check needs."""

    pool_index: int
    latency_s: float
    n_pairs: int
    labels: np.ndarray
    crowdsourced: np.ndarray
    round_sizes: List[int]
    n_rounds: int
    spent_cents: float
    candidates: Optional[tuple] = None   # (rows, cols, likelihood) if embedded
    ok: bool = True                       # set by the check


@dataclasses.dataclass
class Record:
    """Everything a metric reader may read about one run."""

    cell: Cell
    setup_s: float = 0.0
    window_s: float = 0.0
    served: List[Served] = dataclasses.field(default_factory=list)
    failed: int = 0
    spans: List[tuple] = dataclasses.field(default_factory=list)
    dispatches: int = 0
    compiles: int = 0
    trace: Optional[object] = None        # bench.trace.Reduction
    device_kind: str = ""

    def span_durations(self, name: str) -> List[float]:
        return [t1 - t0 for n, t0, t1, _ in self.spans if n == name]


class CompileCounter:
    """Backend compiles seen through ``jax.monitoring`` (registered once
    per process; JAX has no way to unregister a listener)."""

    count = 0
    _registered = False

    @classmethod
    def listen(cls) -> None:
        import jax

        if cls._registered:
            return

        def on_event(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        cls._registered = True


# ---------------------------------------------------------------------------
# serving one batch
# ---------------------------------------------------------------------------
class Client:
    """Submits a cell's sessions to one ``JoinService`` and collects what
    each returned.  ``service_cls`` and the crowd classes are the program's;
    tests pass a broken service here to see the check refuse it."""

    def __init__(self, cell: Cell, service_cls=None):
        from repro.core import PerfectCrowd
        from repro.serve.join_service import JoinService

        self.cell = cell
        t = cell.traffic
        self.svc = (service_cls or JoinService)(lanes=t["lanes"])
        if t["crowd"]["kind"] != "perfect":
            raise ValueError(f"unknown crowd kind {t['crowd']['kind']!r}")
        self.make_crowd = PerfectCrowd
        self.machine = None
        if t["submit"] == "embeddings":
            self.machine = self._machine_args(t["machine"])

    def _machine_args(self, m: dict) -> dict:
        from repro.launch.mesh import make_host_mesh

        args = {"capacity": m["capacity"], "mesh": make_host_mesh(1, 1)}
        if m["path"] == "blocked":
            from repro.kernels.pair_scores.blocking import BlockingConfig

            args["blocking"] = BlockingConfig.for_recall(
                m["recall_floor"], self.cell.config["threshold"],
                n_bits=m["n_bits"], bn=m["bn"], bm=m["bm"],
                tiles_per_call=m["tiles_per_call"])
        elif m["path"] == "dense":
            args["mesh"] = make_host_mesh(*m["mesh"])
        else:
            raise ValueError(f"unknown machine path {m['path']!r}")
        return args

    def submit(self, sess: dict, crowd, spans: list):
        """Submit one session; returns (rid, candidates or None)."""
        import jax

        if self.machine is None:
            from repro.core.pairs import PairSet

            ps = PairSet(sess["u"], sess["v"], sess["likelihood"],
                         sess["truth"], n_objects=sess["n_objects"])
            return self.svc.submit(
                ps, crowd,
                total_true_matches=sess["total_true_matches"]), None
        ent_a, ent_b = sess["ent_a"], sess["ent_b"]
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.machine_phase"):
            rid = self.svc.submit_embeddings(
                sess["a"], sess["b"], sess["threshold"],
                crowd=crowd, truth_fn=lambda r, c: ent_a[r] == ent_b[c],
                **self.machine)
        spans.append(("machine_phase", t0, time.perf_counter(), rid))
        req = self.svc.queue[-1]
        if req.rid != rid:
            raise RuntimeError("submitted request is not last in the queue")
        n_a = int(sess["a"].shape[0])
        cand = (req.pairs.u.copy(), req.pairs.v - n_a,
                req.pairs.likelihood.copy())
        return rid, cand

    def serve_batch(self, pool: list, indices: List[int], spans: list
                    ) -> List[Served]:
        """Submit the pool sessions ``indices``, run the service once, and
        return what each session got, latency from the start of its own
        submit to the return of ``run()``."""
        import jax

        starts, rids, cands = [], [], []
        for k in indices:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.submit"):
                rid, cand = self.submit(pool[k], self.make_crowd(), spans)
            spans.append(("submit", t0, time.perf_counter(), rid))
            starts.append(t0)
            rids.append(rid)
            cands.append(cand)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.run"):
            results = self.svc.run()
        t1 = time.perf_counter()
        spans.append(("run", t0, t1, len(indices)))
        out = []
        for k, rid, ts, cand in zip(indices, rids, starts, cands):
            r = results.get(rid)
            if r is None:
                continue
            out.append(Served(
                pool_index=k, latency_s=t1 - ts, n_pairs=len(r.labels),
                labels=np.asarray(r.labels, bool),
                crowdsourced=np.asarray(r.crowdsourced, bool),
                round_sizes=list(r.round_sizes), n_rounds=r.n_rounds,
                spent_cents=float(r.n_spent_cents), candidates=cand))
        return out


def batches(cell: Cell, seed: int):
    """Pool indices of each batch: the pool in a fixed order drawn from
    the seed, cycled."""
    t = cell.traffic
    order = np.random.default_rng([seed, 7]).permutation(t["pool"])
    i = 0
    while True:
        yield [int(order[(i + j) % len(order)]) for j in range(t["batch"])]
        i += t["batch"]


def make_pool(cell: Cell, seed: int) -> list:
    return cell.generator.pool(cell.config, seed, cell.traffic["pool"])


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def check_devices(cell: Cell, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX "
                     f"found {len(devs)}")
    return devs


TRACE_SECONDS = 5.0   # length of the traced window of a --trace 1 run


def serve_window(client: Client, pool: list, sched, seconds: float,
                 rec: Record) -> None:
    """Serve batches until ``seconds`` have passed; the batch under way at
    the deadline is finished."""
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline:
        idx = next(sched)
        try:
            got = client.serve_batch(pool, idx, rec.spans)
        except Exception:  # a session that raised counts as failed
            traceback.print_exc(file=sys.stderr)
            rec.failed += len(idx)
            break
        rec.failed += len(idx) - len(got)
        rec.served.extend(got)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True, service_cls=None
        ) -> dict:
    """One run of a cell; returns the result line as a dict.

    The measured window runs untraced.  With ``trace`` a second window of
    ``TRACE_SECONDS`` (at least one batch) follows under the profiler; the
    per-layer metrics read the spans and counters of the first window and
    the device trace of the second."""
    import jax

    devs = check_devices(cell, require_chip)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    CompileCounter.listen()
    from repro.core.jax_graph import engine_dispatches

    rec = Record(cell=cell, device_kind=devs[0].device_kind)
    pool = make_pool(cell, seed)
    client = Client(cell, service_cls)
    sched = batches(cell, seed)
    # warm-up: one pass over the pool compiles (or loads) every program the
    # window runs
    n_warm = -(-cell.traffic["pool"] // cell.traffic["batch"])
    for _ in range(n_warm):
        client.serve_batch(pool, next(sched), [])
    d0, c0 = engine_dispatches.count, CompileCounter.count
    t_w0 = time.perf_counter()
    rec.setup_s = t_w0 - t_start
    serve_window(client, pool, sched, seconds, rec)
    rec.window_s = time.perf_counter() - t_w0
    rec.dispatches = engine_dispatches.count - d0
    rec.compiles = CompileCounter.count - c0
    trace_dir = None
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_out", "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        jax.profiler.start_trace(trace_dir)
        serve_window(client, pool, sched, TRACE_SECONDS, rec)
        jax.profiler.stop_trace()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell.chips])
    del client
    from bench import check

    t_c0 = time.perf_counter()
    checks = check.check_run(cell, pool, rec)
    runs = [round(t1 - t0, 3) for n, t0, t1, _ in rec.spans if n == "run"]
    print(f"bench: setup {rec.setup_s:.2f} s, window {rec.window_s:.2f} s "
          f"({len(runs)} run() calls: {runs}), check "
          f"{time.perf_counter() - t_c0:.2f} s", file=sys.stderr)
    line = {"correct": all(c["ok"] for c in checks.values())
            and rec.failed == 0 and len(rec.served) > 0,
            "attempted": len(rec.served) + rec.failed,
            "failed": rec.failed + sum(1 for s in rec.served if not s.ok)}
    if trace:
        from bench import trace as trace_mod

        rec.trace = trace_mod.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    group = "per_layer" if trace else "end_to_end"
    for m in cell.metrics:
        if m["group"] != group:
            continue
        value = cell.reader(m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    line.update(metrics=metrics, device=device)
    if trace:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        line["breakdown"] = rec.trace.breakdown()
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    return line
