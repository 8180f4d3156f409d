#!/usr/bin/env python3
"""Bring-up smoke test for one TPU: the streaming clustering service, end
to end, at the KDD Cup 1999 10% shape, through the entry points a user
calls (``PipelineConfig`` -> ``Session``).

    python3 chip_smoke.py               # one chip: the served path
    python3 chip_smoke.py --four-chips  # four chips: the shard_map paths

One chip.  494,021 synthetic KDD-like records (34 features, 0.93% planted
outliers in 20 small attack clusters, ``kdd_like``), k=3 (the paper's
Table 3), t = the planted count, l2sq, ``kernels.backend="auto"``, a
stream topology whose tree really summarizes, merges over several levels
and refreshes on cadence.  Phases: ingest in batches, refresh, then score
a few thousand queries (planted outliers plus inliers) with ``score()``
and again through ``score_stream`` from 4 client threads.  Checks:

* every main-path op (``min_argmin``, ``lloyd_step``, ``score``) was
  served by ``pallas`` (the ``kernels.dispatch`` counters), and the
  lowered score and pdist programs hold a ``tpu_custom_call``;
* ``score_stream`` results are bit-identical to ``score()``;
* a plain f32 numpy reference on the host, from the same centers and
  threshold: argmin agrees except at near-ties, distance and score agree
  within ``DIST_TOL_ULPS`` (below), the ``is_outlier`` decisions match;
* recall and precision of the planted outliers among the queries are no
  worse than the same configuration and seed give on the CPU.

Four chips.  The sharded service with one site per chip
(``use_shard_map=True``) against host-simulated sites on the same stream:
refreshed model and scores bit-identical.  A oneshot ``Session`` with
``use_shard_map`` against ``distributed_cluster`` called directly on the
same mesh (bitwise) and against host-simulated ``simulate_coordinator``
(same algorithm, other summary code): each path's summary keeps the
planted mass, and the records its k-means-- flags carry it.

Earlier lines report ``device_kind``, per-phase wall times with the
compile time inside each phase, the tree's levels and records, the
refresh count, the backend that served each op, and every check with its
numbers.  The last line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
With no TPU, or when a check fails, the script exits non-zero and prints
no such line.  It has no CPU mode: ``tests/test_chip_smoke.py`` calls the
phase functions below at a tiny size on the CPU instead.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402

# Recall and precision of the planted outliers among the queries, for the
# default KDD configuration and seed, measured on the CPU (JAX_PLATFORMS=cpu,
# kernels served by the "blocked" backend; host of a TPU v5e machine) by
#   JAX_PLATFORMS=cpu python3 -c "import chip_smoke as s; \
#       print(s.run_served_path(s.KDD).quality)"
# which printed {'recall': 1.0, 'precision': 1.0, 'flagged': 1024,
# 'planted': 1024}.  The chip must do at least as well.
CPU_RECALL = 1.0
CPU_PRECISION = 1.0

# Distances on the chip are x2 + c2 - 2 x.c in f32, on the host sum((x-c)^2)
# in f32.  Both round at most ~d units of 2^-24 per term, so the two agree
# within DIST_TOL_ULPS * 2^-23 * (|x|^2 + |c|^2).  A matmul run at bf16
# precision misses this by two orders of magnitude.
DIST_TOL_ULPS = 64
_EPS32 = float(np.finfo(np.float32).eps)

# Four chips, oneshot sessions.  Summaries conserve mass, so the records
# with planted ids should carry all t planted points; on a TPU v5 lite at
# the KDD shape both paths read 4580.0 of 4580, as at n=60,000 on four
# virtual CPU devices.  Outlier-mass recall (planted mass in the records
# k-means-- flags, over t) read 1.0 on both paths at n=60,000 on the CPU.
PLANTED_MASS_TOL = 0.01
OUTLIER_MASS_RECALL_MIN = 0.99


@dataclasses.dataclass(frozen=True)
class Size:
    """One smoke configuration; the default is the KDD Cup 1999 10% set."""
    n: int = 494_021
    d: int = 34
    t_frac: float = 0.0093
    k: int = 3
    leaf_size: int = 65_536
    refresh_every: int = 131_072
    micro_batch: int = 256
    ingest_batch: int = 16_384
    query_outliers: int = 1_024
    query_inliers: int = 3_072
    clients: int = 4
    seed: int = 0


KDD = Size()
# The four-chip comparison refreshes once, at the end: every cadence refresh
# would compile the gathered program again for its larger root, at four
# times the chip cost, and the bit-identity it checks is the same.
KDD_FOUR_CHIPS = dataclasses.replace(KDD, refresh_every=1 << 20)


@dataclasses.dataclass
class Report:
    checks: dict = dataclasses.field(default_factory=dict)
    quality: dict = dataclasses.field(default_factory=dict)
    backends: dict = dataclasses.field(default_factory=dict)
    session: object = None    # the served Session, for the lowering checks
    queries: object = None

    def say(self, msg: str) -> None:
        print(msg, flush=True)

    def check(self, name: str, ok: bool, detail: str) -> None:
        self.checks[name] = bool(ok)
        self.say(f"check {name}: {'PASS' if ok else 'FAIL'} ({detail})")

    @property
    def ok(self) -> bool:
        return bool(self.checks) and all(self.checks.values())


class _CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (its own
    monitoring events), so each phase reports compile time apart."""

    def __init__(self):
        self.total = 0.0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.total += duration


@functools.lru_cache(maxsize=None)
def _clock() -> _CompileClock:
    return _CompileClock()


def _phase(rep: Report, name: str, fn, *args, **kw):
    clock = _clock()
    c0, t0 = clock.total, time.perf_counter()
    out = fn(*args, **kw)
    wall = time.perf_counter() - t0
    rep.say(f"phase {name}: wall {wall:.3f} s, of which compile "
            f"{clock.total - c0:.3f} s")
    return out


# ------------------------------------------------------------------ phases
def make_data(size: Size):
    from repro.data.synthetic import kdd_like
    return kdd_like(n=size.n, d=size.d, t_frac=size.t_frac, seed=size.seed)


def make_session(size: Size, t: int, **topology):
    from repro import Session, pipeline_config
    cfg = pipeline_config(
        dim=size.d, k=size.k, t=t, metric="l2sq", kernels="auto",
        seed=size.seed, micro_batch=size.micro_batch,
        # every query is admitted (no shedding): the smoke checks results
        serving={"queue_bound": size.query_outliers + size.query_inliers,
                 "shed_policy": "wait", "batch_window_ms": 1.0},
        **topology)
    return Session(cfg)


def ingest(session, x, batch: int) -> None:
    for i in range(0, x.shape[0], batch):
        session.ingest(x[i:i + batch])


def pick_queries(x, out_ids, size: Size):
    """Planted outliers then inliers, drawn without replacement."""
    rng = np.random.default_rng(size.seed + 1)
    inliers = np.setdiff1d(np.arange(x.shape[0]), out_ids)
    ids = np.concatenate([
        rng.choice(out_ids, min(size.query_outliers, out_ids.size), False),
        rng.choice(inliers, min(size.query_inliers, inliers.size), False)])
    return x[ids], np.isin(ids, out_ids)


def score_concurrent(session, q, clients: int):
    """The same rows through ``score_stream`` from ``clients`` threads."""
    parts = np.array_split(np.arange(q.shape[0]), clients)
    slots = [None] * clients
    errors = []

    def client(i):
        try:
            slots[i] = list(session.score_stream(q[parts[i]], timeout=600.0))
        except Exception as e:   # re-raised on the main thread below
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]
    return [r for s in slots for r in s]


def host_reference(q, centers, threshold):
    """Plain f32 numpy: distance to every center, nearest and runner-up."""
    q = np.asarray(q, np.float32)
    c = np.asarray(centers, np.float32)
    dist = np.square(q[:, None, :] - c[None, :, :]).sum(-1, dtype=np.float32)
    order = np.argsort(dist, axis=1, kind="stable")
    rows = np.arange(q.shape[0])
    best = dist[rows, order[:, 0]]
    second = (dist[rows, order[:, 1]] if c.shape[0] > 1
              else np.full_like(best, np.inf))
    thr = np.float32(max(float(threshold), 1e-30))
    tol = (DIST_TOL_ULPS * _EPS32
           * (np.square(q).sum(1) + np.square(c[order[:, 0]]).sum(1)))
    return dict(center=order[:, 0], dist=best, second=second,
                score=best / thr, threshold=thr, tol=tol.astype(np.float32))


def check_reference(rep: Report, results, ref) -> None:
    center = np.array([r.center for r in results])
    dist = np.array([r.distance for r in results], np.float32)
    score = np.array([r.outlier_score for r in results], np.float32)
    flag = np.array([r.is_outlier for r in results])
    tol, thr = ref["tol"], ref["threshold"]
    tie = (ref["second"] - ref["dist"]) <= tol
    bad_arg = (center != ref["center"]) & ~tie
    rep.check("argmin_vs_host",
              not bad_arg.any(),
              f"{int((center != ref['center']).sum())} differ of "
              f"{center.size}, {int(tie.sum())} near-ties exempt, "
              f"{int(bad_arg.sum())} outside ties")
    err = np.abs(dist - ref["dist"])
    ratio = float((err / tol).max())
    rep.check("distance_vs_host", ratio <= 1.0,
              f"max |err| {float(err.max()):.3e}, max err/tol {ratio:.4f}, "
              f"tol = {DIST_TOL_ULPS} * eps32 * (|x|^2 + |c|^2)")
    serr = np.abs(score - ref["score"])
    sratio = float((serr / (tol / thr + 2 * _EPS32 * ref["score"])).max())
    rep.check("score_vs_host", sratio <= 1.0,
              f"max |err| {float(serr.max()):.3e}, max err/tol {sratio:.4f}")
    boundary = np.abs(ref["dist"] - thr) <= tol
    bad_flag = (flag != (ref["score"] > 1.0)) & ~boundary
    rep.check("is_outlier_vs_host", not bad_flag.any(),
              f"{int(bad_flag.sum())} decisions differ, "
              f"{int(boundary.sum())} within tol of the threshold")


def bit_identical(a, b) -> bool:
    return len(a) == len(b) and all(
        x.center == y.center and x.distance == y.distance
        and x.outlier_score == y.outlier_score
        and x.is_outlier == y.is_outlier for x, y in zip(a, b))


def outlier_quality(results, truth) -> dict:
    flag = np.array([r.is_outlier for r in results])
    tp = int((flag & truth).sum())
    return {"recall": tp / max(int(truth.sum()), 1),
            "precision": tp / max(int(flag.sum()), 1),
            "flagged": int(flag.sum()), "planted": int(truth.sum())}


def served_backends(stats: dict) -> dict:
    """{op: {backend: count}} from the ``kernels.dispatch`` counters."""
    from repro.obs.registry import split_key
    out: dict = {}
    for key, v in stats.get("counters", {}).items():
        name, labels = split_key(key)
        if name == "kernels.dispatch" and v:
            out.setdefault(labels["op"], {})[labels["backend"]] = int(v)
    return out


def lowered_texts(session, q):
    """StableHLO of the served score program and of pdist, as lowered."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.pdist.ops import min_argmin
    from repro.stream.service import _score_batch
    cfg, model = session.engine.cfg, session.model
    xb = jnp.zeros((cfg.micro_batch, cfg.dim), jnp.float32)
    score_txt = _score_batch.lower(
        xb, model.centers, model.threshold, metric=cfg.metric,
        policy=cfg.policy).as_text()
    pdist = jax.jit(functools.partial(min_argmin, metric=cfg.metric,
                                      policy=cfg.policy))
    pdist_txt = pdist.lower(jnp.asarray(q, jnp.float32),
                            model.centers).as_text()
    return score_txt, pdist_txt


def run_served_path(size: Size = KDD) -> Report:
    """Ingest -> refresh -> score -> score_stream on one device, with every
    check that holds on any platform."""
    rep = Report()
    x, out_ids = _phase(rep, "data (host, set-up)", make_data, size)
    t = int(out_ids.size)
    rep.say(f"data: {x.shape[0]} records x {x.shape[1]} features, "
            f"{t} planted outliers; k={size.k} t={t} leaf_size="
            f"{size.leaf_size} refresh_every={size.refresh_every}")
    session = make_session(size, t, topology="stream",
                           leaf_size=size.leaf_size,
                           refresh_every=size.refresh_every)
    _phase(rep, "ingest", ingest, session, x, size.ingest_batch)
    _phase(rep, "refresh", session.refresh)
    tree = session.engine.tree
    levels = sorted({nd.level for nd in tree.nodes})
    refreshes = int(session.model.version)
    rep.say(f"tree: {len(tree.nodes)} summaries on levels {levels}, "
            f"{tree.num_records} records ({tree._buf_n} buffered), "
            f"{refreshes} refreshes, model v{refreshes} fit on "
            f"{session.last_fit.records_folded} records")
    rep.check("tree_summarizes", max(levels, default=0) >= 1
              and tree.num_records < x.shape[0] // 2 and refreshes >= 2,
              f"max level {max(levels, default=0)}, {tree.num_records} "
              f"records for {x.shape[0]} points, {refreshes} refreshes")

    q, truth = pick_queries(x, out_ids, size)
    sync = _phase(rep, "score (sync)", session.score, q)
    conc = _phase(rep, f"score_stream ({size.clients} clients)",
                  score_concurrent, session, q, size.clients)
    session.close()
    rep.check("score_stream_bit_identical", bit_identical(sync, conc),
              f"{len(conc)} rows from {size.clients} threads vs score()")

    model = session.model
    check_reference(rep, sync, host_reference(q, model.centers,
                                              model.threshold))
    rep.quality = outlier_quality(sync, truth)
    rep.say("quality: " + json.dumps(rep.quality))
    rep.backends = served_backends(session.stats())
    rep.say("served by: " + json.dumps(rep.backends, sort_keys=True))
    rep.session, rep.queries = session, q
    return rep


def tpu_checks(rep: Report, cpu_recall, cpu_precision) -> None:
    """The checks that only mean something on the chip."""
    main_ops = ("min_argmin", "lloyd_step", "score")
    rep.check("pallas_served",
              all(set(rep.backends.get(op, {})) == {"pallas"}
                  for op in main_ops),
              json.dumps({op: rep.backends.get(op) for op in main_ops},
                         sort_keys=True))
    score_txt, pdist_txt = lowered_texts(rep.session, rep.queries)
    rep.check("tpu_custom_call",
              "tpu_custom_call" in score_txt
              and "tpu_custom_call" in pdist_txt,
              f"score: {'tpu_custom_call' in score_txt}, "
              f"pdist: {'tpu_custom_call' in pdist_txt}")
    qual = rep.quality
    rep.check("quality_vs_cpu",
              cpu_recall is not None and cpu_precision is not None
              and qual["recall"] >= cpu_recall
              and qual["precision"] >= cpu_precision,
              f"recall {qual['recall']:.6f} vs CPU {cpu_recall}, precision "
              f"{qual['precision']:.6f} vs CPU {cpu_precision}")


# ------------------------------------------------------------ four chips
def run_four_chips(size: Size = KDD_FOUR_CHIPS, n_sites: int = 4) -> Report:
    """shard_map paths over ``n_sites`` devices against their host-side
    counterparts on the same data."""
    import jax
    import jax.numpy as jnp
    from repro.core import distributed_cluster, sites_mesh
    from repro.core.metrics import outlier_scores

    rep = Report()
    x, out_ids = _phase(rep, "data (host, set-up)", make_data, size)
    t = int(out_ids.size)
    q, truth = pick_queries(x, out_ids, size)

    # sharded stream: one site per device vs host-simulated sites
    runs = {}
    for use_sm in (True, False):
        sess = make_session(size, t, topology="sharded", sites=n_sites,
                            use_shard_map=use_sm, leaf_size=size.leaf_size,
                            refresh_every=size.refresh_every)
        label = "shard_map" if use_sm else "host-sim"
        _phase(rep, f"sharded ingest [{label}]", ingest, sess, x,
               size.ingest_batch)
        model = _phase(rep, f"sharded refresh [{label}]", sess.refresh)
        res = _phase(rep, f"sharded score [{label}]", sess.score, q)
        st = sess.engine.last_refresh
        rep.say(f"sharded [{label}]: path {st.path}, v{st.version}, "
                f"{st.comm_records} records gathered, {st.comm_bytes} bytes")
        runs[label] = (model, res, st)
    (m1, r1, s1), (m2, r2, s2) = runs["shard_map"], runs["host-sim"]
    differ = [f"{f} {np.asarray(a).tolist()} vs {np.asarray(b).tolist()}"
              if np.asarray(a).size == 1 else f
              for f, a, b in zip(m1._fields, m1, m2)
              if not np.array_equal(np.asarray(a), np.asarray(b))]
    rep.check("sharded_shard_map_path", s1.path == "shard_map"
              and s2.path == "host-sim", f"{s1.path} vs {s2.path}")
    rep.check("sharded_model_bit_identical", not differ,
              "centers, threshold, cost, version, trained weight"
              + (f"; differ: {', '.join(differ)}" if differ else ""))
    rep.check("sharded_model_on_one_device",
              all(len(a.sharding.device_set) == 1 for a in m1),
              "the gathered model feeds single-device score programs")
    rep.check("sharded_scores_bit_identical", bit_identical(r1, r2),
              f"{len(r1)} queries")

    # oneshot: Session(use_shard_map) vs distributed_cluster vs host-sim
    xs = x[: (x.shape[0] // n_sites) * n_sites]
    planted = out_ids[out_ids < xs.shape[0]]
    quals = {}
    for use_sm in (True, False):
        sess = make_session(size, t, topology="oneshot", sites=n_sites,
                            use_shard_map=use_sm)
        label = "shard_map" if use_sm else "simulate_coordinator"
        _phase(rep, f"oneshot fit [{label}]", sess.fit, xs)
        r = sess.result
        if use_sm:
            sm_result, cfg = r, sess.config
        w = np.asarray(r["summary_weights"])
        in_summ = np.isin(r["summary_ids"], planted)
        flagged = np.isin(r["summary_ids"], r["outlier_ids"])
        ids = outlier_scores(planted, r["summary_ids"], r["outlier_ids"])
        quals[label] = {
            "scored": outlier_quality(sess.score(q), truth),
            "summary_records": int(r["comm_records"]),
            "planted_mass_in_summary": float(w[in_summ].sum()),
            "outlier_mass_recall": float(w[in_summ & flagged].sum()) / t,
            "id_pre_recall": ids.pre_recall, "id_recall": ids.recall}
    direct = _phase(rep, "oneshot distributed_cluster (direct)",
                    distributed_cluster,
                    jnp.asarray(xs).reshape(n_sites, -1, xs.shape[1]),
                    jax.random.key(cfg.seed), sites_mesh(n_sites),
                    k=size.k, t=t, summarizer=cfg.summarizer,
                    second_iters=cfg.second_iters, policy=cfg.kernels)
    dout = np.asarray(direct.outlier_ids)
    rep.check("oneshot_session_vs_distributed_cluster",
              np.array_equal(sm_result["centers"],
                             np.asarray(direct.centers))
              and np.array_equal(sm_result["outlier_ids"], dout[dout >= 0])
              and sm_result["cost"] == float(direct.cost),
              "centers, outlier ids and cost bitwise")
    rep.say(f"oneshot ({planted.size} planted): "
            + json.dumps(quals, sort_keys=True))
    # Same algorithm, other summary code (fixed-shape vs host-compacted
    # sites), so the two keep different records.  A sample inside an attack
    # cluster folds its neighbours into one weighted record, which keeps
    # their mass but drops their ids, so id-level recall (printed above)
    # differs between the paths without any outlier being lost.  Both are
    # held to what that rests on: the summary keeps the planted mass, and
    # the records k-means-- flags carry it.
    paths = ("shard_map", "simulate_coordinator")
    mass = {p: quals[p]["planted_mass_in_summary"] for p in paths}
    rep.check("oneshot_planted_mass_in_summary",
              all(abs(m - t) <= PLANTED_MASS_TOL * t for m in mass.values()),
              ", ".join(f"{p} {m:.1f}" for p, m in mass.items())
              + f" of t={t}, within {PLANTED_MASS_TOL:.0%}")
    recall = {p: quals[p]["outlier_mass_recall"] for p in paths}
    rep.check("oneshot_outlier_mass_recall",
              all(r >= OUTLIER_MASS_RECALL_MIN for r in recall.values()),
              ", ".join(f"{p} {r:.4f}" for p, r in recall.items())
              + f", each >= {OUTLIER_MASS_RECALL_MIN}")
    return rep


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard_map paths, one site per chip, "
                         "against their host-side counterparts")
    args = ap.parse_args(argv)

    cache = compile_cache.enable()
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform {platform!r} "
              f"({len(devices)} device(s))", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU chip(s), found {len(devices)}",
              file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    print(f"device_kind: {kind}; {len(devices)} device(s); compile cache "
          f"{cache}", flush=True)

    t0 = time.perf_counter()
    if args.four_chips:
        rep = run_four_chips(KDD_FOUR_CHIPS, n_sites=4)
    else:
        rep = run_served_path(KDD)
        tpu_checks(rep, CPU_RECALL, CPU_PRECISION)
    print(f"total wall {time.perf_counter() - t0:.3f} s; "
          f"{sum(rep.checks.values())}/{len(rep.checks)} checks passed",
          flush=True)
    if not rep.ok:
        failed = [k for k, v in rep.checks.items() if not v]
        print(f"chip_smoke: FAILED {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
