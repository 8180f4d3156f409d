#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload kdd99.ingest --seed 7 --seconds 10 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<name>.json``: the population, the ``repro.Session`` pipeline,
the warm-up and the limits of the correctness check) and a traffic mix
(``traffic/<name>.json``, read by ``loadgen.py``).  Each metric is read by
``metrics/<name>.py``.  Nothing here is specific to one of them.

Set-up (``setup_s``, from process start to the window): the population is
made from ``--seed``, the session built, and the warm-up ingests the
configuration's ``warmup_points`` and then whole refresh periods until two
in a row pass with nothing traced (cap: ``warmup_max_points``); a mix
with score traffic then refreshes and scores once.  The window runs the mix
for ``--seconds``.  With ``--trace 1`` the JAX profiler records the window
and the cell's per-layer metrics are reported instead of its end-to-end
ones.  After the window the answers are checked against the plain
reference (``reference.py``).

Standard error ends with each compared number beside its limit; the last
line of standard output is one JSON object.  With no TPU, or fewer chips
than the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import data, loadgen, reference, trace_reduce  # noqa: E402
from bench.metrics_common import answered  # noqa: E402

clock = loadgen.clock


class BenchError(Exception):
    """The cell cannot run here (no chip, unknown name, missing file)."""


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------ lookup
def load_spec() -> dict:
    path = ROOT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path.name} at {ROOT}")
    return json.loads(path.read_text())


def find(entries: list, name: str, kind: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"unknown {kind} {name!r}; known: "
                     f"{sorted(e['name'] for e in entries)}")


def load_json(path: Path, kind: str) -> dict:
    if not path.is_file():
        raise BenchError(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json", "traffic")


def reader(metric: str):
    """The module ``metrics/<metric>.py``; its ``read(run)`` gives the
    metric's value or None when the run has nothing to read."""
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"no reader for metric {metric!r} at "
                         f"{path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, workload: str, trace: bool) -> list[dict]:
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


# ------------------------------------------------------------ JAX
def cache_dir() -> str:
    """JAX's persistent compilation cache: a fixed directory inside the
    checkout (``$JAX_COMPILATION_CACHE_DIR`` is overridden, so that two
    checkouts on one machine share no cache).  ``$BENCH_COMPILE_CACHE``,
    where set, names another directory, to share compiled programs across
    checkouts on purpose."""
    return os.environ.get("BENCH_COMPILE_CACHE") or str(BENCH / ".jax_cache")


def start_jax(chips: int, require_chip: bool):
    import jax
    devices = jax.devices()
    if devices[0].platform == "tpu":
        jax.config.update("jax_compilation_cache_dir", cache_dir())
        # every program, however quick to compile, comes from the cache on
        # the second run of a cell
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if require_chip:
        if devices[0].platform != "tpu":
            raise BenchError(f"needs a TPU; JAX found platform "
                             f"{devices[0].platform!r}")
        if len(devices) < chips:
            raise BenchError(f"needs {chips} TPU chips, found "
                             f"{len(devices)}")
    return jax, devices


class CompileCount:
    """JAX traces and compiles, counted while armed."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traced",
              "/jax/core/compile/backend_compile_duration": "compiled"}
    _installed = None

    def __init__(self):
        self.armed = False
        self.counts = {"traced": 0, "compiled": 0}

    @classmethod
    def get(cls) -> "CompileCount":
        if cls._installed is None:
            import jax
            cls._installed = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._installed._on)
        return cls._installed

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def arm(self) -> None:
        self.counts = {"traced": 0, "compiled": 0}
        self.armed = True

    def disarm(self) -> dict:
        self.armed = False
        return dict(self.counts)


# ------------------------------------------------------------ the system
def program_seed(seed: int) -> int:
    """The session's seed: 31 bits drawn from the run's seed."""
    return int(data.rng_for(seed, 1).integers(0, 2**31 - 1))


def trees_of(engine) -> list:
    return list(engine.trees) if hasattr(engine, "trees") else [engine.tree]


def snapshot_model(model) -> dict:
    return {"centers": np.asarray(model.centers, np.float32),
            "threshold": float(model.threshold),
            "trained_weight": float(model.trained_weight)}


def snapshot_trees(engine) -> list[dict]:
    """The root a refresh was fit on, per tree (``root()``: its live
    summaries and leaf buffer, copied), with the raw-point spans of the
    live summaries."""
    out = []
    for tr in trees_of(engine):
        pts, w, _ = tr.root()
        out.append({"points": pts, "weights": w,
                    "spans": [(nd.min_seq, nd.max_seq) for nd in tr.nodes],
                    "total": tr.total_ingested,
                    "window": tr.cfg.window})
    return out


def counter_delta(obs0: dict, obs1: dict, name: str) -> float:
    """What the window added to the program's counter ``name`` (all label
    sets), from two ``obs.snapshot()``s."""
    def tot(snap):
        return sum(v for k, v in snap["counters"].items()
                   if k.split("{", 1)[0] == name)
    return tot(obs1) - tot(obs0)


class RefreshLog:
    """Times every refresh the engine runs, cadence ones included (from
    trigger to install; the engine's ``refresh``, which cadence refreshes
    call, is wrapped on the instance), and keeps the root and model of two
    for the check: the window's first refresh and one drawn from the seed
    (uniform over the rest, decided before the refresh, so that only the
    kept ones are copied)."""

    def __init__(self, session, rng: np.random.Generator):
        self.engine = session.engine
        self.rng = rng
        self.armed = False
        self.times: list[tuple[float, float]] = []
        self.kept: dict = {}
        self.seen = 0
        orig = self.engine.refresh

        def timed(*args, **kw):
            keep = self._slot() if self.armed else None
            with loadgen.annotate("bench.refresh"):
                t0 = clock()
                out = orig(*args, **kw)
                t1 = clock()
            if self.armed:
                self.times.append((t0, t1))
            if keep is not None:
                self.kept[keep] = {"model": snapshot_model(self.engine.model),
                                   "trees": snapshot_trees(self.engine)}
            return out

        self.engine.refresh = timed

    def _slot(self):
        self.seen += 1
        if self.seen == 1:
            return "first"
        if self.rng.random() < 1.0 / (self.seen - 1):
            return "drawn"                 # uniform over refreshes 2..n
        return None

    def arm(self) -> None:
        self.armed, self.times, self.kept, self.seen = True, [], {}, 0

    def disarm(self) -> None:
        self.armed = False


class Cell:
    """One workload: set-up, window and check, driven by name."""

    def __init__(self, workload: str, seed: int, *, spec: dict | None = None,
                 config: dict | None = None):
        self.spec = spec if spec is not None else load_spec()
        self.workload = find(self.spec["workloads"], workload, "workload")
        entry = find(self.spec["configs"], self.workload["config"],
                     "configuration")
        self.config = (config if config is not None
                       else load_json(ROOT / entry["file"], "configuration"))
        self.mix = traffic(self.workload["traffic"])
        self.seed = seed
        self.chips = int(self.workload["chips"])

    # -------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro import Session, pipeline_config
        cfg = self.config
        t0 = clock()
        self.x, self.planted = data.population(cfg["population"], self.seed)
        self.t_data = clock() - t0
        kw = dict(cfg["pipeline"])
        kw["seed"] = program_seed(self.seed)
        self.session = Session(pipeline_config(**kw))
        self.refreshes = RefreshLog(self.session, data.rng_for(self.seed, 2))
        self.stream = loadgen.Cycle(self.x)
        self.compiles = CompileCount.get()
        self._warm_up()

    def _warm_up(self) -> None:
        cfg, s = self.config, self.session
        batch = int(cfg["warmup_batch"])
        period = int(cfg["pipeline"]["refresh_every"])
        while self.stream.fed < int(cfg["warmup_points"]):
            s.ingest(self.stream.take(batch))
        # whole refresh periods until two in a row trace nothing new
        quiet = 0
        while (self.mix.get("ingest") and quiet < 2
               and self.stream.fed < int(cfg["warmup_max_points"])):
            self.compiles.arm()
            goal = self.stream.fed + period
            while self.stream.fed < goal:
                s.ingest(self.stream.take(batch))
            quiet = quiet + 1 if self.compiles.disarm()["traced"] == 0 else 0
        if self.mix.get("score"):
            s.refresh()
            warm = self.x[:int(cfg["pipeline"]["micro_batch"])]
            for t in s.score_stream(warm, timeout=600.0):
                pass

    # -------------------------------------------------------- window
    def score_rate(self) -> float:
        """Offered rows/s: the mix's share of the configuration's knee."""
        return (float(self.mix["score"]["rate_of_knee"])
                * float(self.config["knee_rows_per_s"]))

    def window(self, seconds: float, trace_dir: str | None = None,
               rate: float | None = None) -> dict:
        import jax
        from repro import obs
        plans = None
        if self.mix.get("score"):
            plans = loadgen.score_schedule(
                self.mix["score"], rate or self.score_rate(), seconds,
                self.x.shape[0], data.rng_for(self.seed, 3))
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self.fed_before = self.stream.fed
        box: dict = {}

        def on_open(t_open: float) -> None:
            box["obs0"] = obs.snapshot()
            self.refreshes.arm()
            self.compiles.arm()
            self.t_window = t_open

        with loadgen.annotate("bench.window"):
            samples = loadgen.run_window(self.session, self.stream, self.x,
                                         self.mix, seconds, plans, on_open)
        self.in_window = self.compiles.disarm()
        self.refreshes.disarm()
        samples["obs0"], samples["obs1"] = box["obs0"], obs.snapshot()
        ran = counter_delta(samples["obs0"], samples["obs1"], "refresh.count")
        if ran != len(self.refreshes.times):
            raise BenchError(
                f"the program counted {ran:g} refreshes in the window, the "
                f"harness timed {len(self.refreshes.times)}: refreshes no "
                f"longer go through the engine's refresh")
        samples["spans"] = obs.get_default_recorder().spans()
        samples["refreshes"] = [r for r in self.refreshes.times
                                if r[1] <= samples["t_close"]]
        if trace_dir is not None:
            jax.profiler.stop_trace()
        self.samples = samples
        return samples

    # -------------------------------------------------------- check
    def numbers(self, control: bool = False) -> dict:
        """Every compared number of this run; with ``control`` the
        reference at HIGH takes the program's place."""
        out: dict = {}
        tie = float(self.config["limits"].get("dist_ulps", 0.0))
        if self.mix.get("score"):
            out.update(self._score_numbers(tie, control))
        if self.mix.get("ingest"):
            out.update(self._ingest_numbers(control))
        return out

    def _score_numbers(self, tie: float, control: bool) -> dict:
        """Every row due in the window; shed rows are left out (they
        count in ``failed``), rows never answered or failed are
        ``missing``."""
        rows, got = [], []
        for r in self.samples["requests"]:
            ans = r["answers"] or [None] * r["ids"].size
            for i, a in zip(r["ids"], ans):
                if a != "shed":
                    rows.append(i)
                    got.append(a if isinstance(a, tuple)
                               else (-1, np.nan, np.nan, False))
        x = self.x[np.asarray(rows, np.int64)]
        model = self.session.model
        centers = np.asarray(model.centers)
        if control:
            served = reference.control_served(x, centers,
                                              float(model.threshold))
        else:
            cols = list(zip(*got)) if got else [[], [], [], []]
            served = {"center": np.asarray(cols[0], np.int64),
                      "distance": np.asarray(cols[1], np.float64),
                      "score": np.asarray(cols[2], np.float64),
                      "flag": np.asarray(cols[3], bool)}
        return reference.score_numbers(x, served, centers,
                                       float(model.threshold), tie)

    def _ingest_numbers(self, control: bool) -> dict:
        t = float(self.config["pipeline"]["t"])
        out = {"thr_ulps": None, "center_ulps": None, "trained_gap": None,
               "mass_gap": None, "window_short": None}
        for snap in self.refreshes.kept.values():
            pts = np.concatenate([tr["points"] for tr in snap["trees"]])
            w = np.concatenate([tr["weights"] for tr in snap["trees"]])
            dist = arg = None
            if control:
                dist, arg = reference.control_nearest(
                    pts, snap["model"]["centers"])
                dist = dist.astype(np.float64)
            got = reference.refresh_numbers(pts, w, snap["model"], t,
                                            dist=dist, arg=arg)
            got.update(reference.tree_numbers(snap["trees"]))
            for k, v in got.items():
                out[k] = v if out[k] is None else max(out[k], v)
        fed = sum(tr.total_ingested for tr in trees_of(self.session.engine))
        out["fed_gap"] = float(abs(fed - self.stream.fed))
        return out

    def judge(self, numbers: dict) -> tuple[bool, dict]:
        limits = self.config["limits"]
        checks, ok = {}, True
        for name, value in numbers.items():
            if name not in limits:
                raise BenchError(f"number {name!r} has no limit in the "
                                 f"configuration")
            passed = value is not None and value <= limits[name]
            ok &= passed
            checks[name] = {"value": value, "limit": limits[name]}
        return ok, checks

    def close(self) -> None:
        self.session.close()


# ------------------------------------------------------------ metrics
class Run:
    """What a metric reader sees of one run."""

    def __init__(self, cell: Cell, setup_s: float, device_kind: str,
                 trace: dict | None):
        s = cell.samples
        self.cell, self.config = cell, cell.config
        self.setup_s, self.device_kind, self.trace = setup_s, device_kind, \
            trace
        self.samples = s
        self.window = (s["t_open"], s["t_close"])
        self.refreshes = s["refreshes"]

    def _series(self, section: str, name: str, snap: dict) -> list:
        return [v for k, v in snap[section].items()
                if k.split("{", 1)[0] == name]

    def hist(self, name: str) -> tuple[int, float]:
        """(count, sum) the window added to histogram ``name`` (all
        label sets)."""
        def tot(snap):
            vs = self._series("histograms", name, snap)
            return (sum(v["count"] for v in vs), sum(v["sum"] for v in vs))
        c0, s0 = tot(self.samples["obs0"])
        c1, s1 = tot(self.samples["obs1"])
        return c1 - c0, s1 - s0

    def counter(self, name: str) -> float:
        return counter_delta(self.samples["obs0"], self.samples["obs1"],
                             name)

    def spans(self, name: str) -> list[tuple[float, float]]:
        lo, hi = self.window
        return [(r["t0"], r["t1"]) for r in self.samples["spans"]
                if r["name"] == name and lo <= r["t0"] < hi]


# ------------------------------------------------------------ one run
def run(args, *, require_chip: bool = True, config: dict | None = None,
        spec: dict | None = None) -> dict:
    spec = spec if spec is not None else load_spec()
    cell = Cell(args.workload, args.seed, spec=spec, config=config)
    metrics = [(m, reader(m["name"]))
               for m in cell_metrics(spec, args.workload, bool(args.trace))]
    jax, devices = start_jax(cell.chips, require_chip)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise BenchError(f"the system under test is not here: {e}")
    kind = devices[0].device_kind
    say(f"device: platform {devices[0].platform}, kind {kind}, "
        f"{len(devices)} device(s); the cell uses {cell.chips}; compile "
        f"cache {cache_dir()}")
    t_jax = clock()
    cell.setup()
    t_setup = clock()
    trace_dir = None
    if args.trace:
        trace_dir = args.trace_dir or str(BENCH / ".trace" / args.workload)
    setup_s = t_setup - T_PROCESS
    say(f"set-up: {setup_s:.3f} s (to JAX's devices {t_jax - T_PROCESS:.3f}"
        f" s, population {cell.t_data:.3f} s, session and warm-up "
        f"{t_setup - t_jax - cell.t_data:.3f} s; {cell.stream.fed} points "
        f"ingested in warm-up)")
    samples = cell.window(float(args.seconds), trace_dir)
    say(f"in the window: {cell.in_window['traced']} traced, "
        f"{cell.in_window['compiled']} compiled programs")
    used = devices[:cell.chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    reduced = None
    if trace_dir is not None:
        tr = trace_reduce.load(trace_dir)
        say(f"trace: device planes {tr.lines}, ops from "
            f"{sorted(set(tr.op_line.values()))}")
        if tr.devices:
            reduced = trace_reduce.reduce(
                tr, devices=sorted(tr.devices)[:cell.chips])
            say(f"trace: busy {reduced['busy_s']:.6f} s of "
                f"{reduced['window_s']:.6f} s")
        elif require_chip:
            raise BenchError("the trace holds no device operations")
    view = Run(cell, setup_s, kind, reduced)
    values = {}
    for m, mod in metrics:
        v = mod.read(view)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    attempted, failed = describe(cell, samples)
    cell.close()
    numbers = cell.numbers()
    ok, checks = cell.judge(numbers)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": values, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def describe(cell: Cell, samples: dict) -> tuple[int, int]:
    """Requests attempted and failed in the window; earlier lines say how
    the window went."""
    attempted = failed = 0
    if "ingest" in samples:
        ing = samples["ingest"]
        attempted += len(ing["calls"])
        say(f"ingest: {ing['points']} points in {ing['elapsed']:.6f} s, "
            f"{len(ing['calls'])} calls, {len(samples['refreshes'])} "
            f"refreshes")
    if "requests" in samples:
        reqs = samples["requests"]
        attempted += len(reqs)
        late = np.array([r["submit"] - r["due"] for r in reqs
                         if r["submit"] is not None])
        failed += sum(not answered(r) for r in reqs)
        rows = sum(r["ids"].size for r in reqs)
        lat = np.array([r["done"] - r["due"] for r in reqs if answered(r)])
        if lat.size:
            say(f"score latency: p50 {np.percentile(lat, 50) * 1e3:.3f} ms, "
                f"p99 {np.percentile(lat, 99) * 1e3:.3f} ms, max "
                f"{lat.max() * 1e3:.3f} ms over {lat.size} answered")
        if late.size:
            say(f"score: {len(reqs)} requests, {rows} rows; generator "
                f"late by p50 {np.percentile(late, 50) * 1e3:.3f} ms, p99 "
                f"{np.percentile(late, 99) * 1e3:.3f} ms, max "
                f"{late.max() * 1e3:.3f} ms")
    return attempted, failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="where the profiler writes (default: "
                         "bench/.trace/<workload>)")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except BenchError as e:
        say(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
