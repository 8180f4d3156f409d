"""One verb set over every topology: the ``Session`` facade.

``Session(config)`` builds and drives the layer the config's topology
names — ``simulate_coordinator`` / ``distributed_cluster`` (oneshot),
``StreamService`` (stream) or ``ShardedStreamService`` (sharded) — behind
one interface:

    fit(points)      ingest + refresh in one call; returns the ModelState
    ingest(points)   feed raw points (stream topologies refresh on cadence)
    refresh()        (re)fit the serving model on everything ingested
    score(queries)   nearest-center distance / outlier score per query row
    score_stream(queries)  the same scores through the async serving path
                     (continuous batching + admission control, repro.serve)
    save(dir)        checkpoint everything, config embedded in the manifest
    Session.load(dir)  rebuild topology + policies from the manifest alone

The facade adds **no math of its own**: stream topologies delegate verbs
verbatim to the services, and the oneshot engine calls the same
coordinator entry points a direct caller would, with the same key
(``jax.random.key(config.seed)``) — so Session results are bit-identical
to driving those layers directly with equivalent settings (asserted in
``tests/test_api.py``).

Oneshot scoring: the coordinator layers return centers and outlier ids
but no serving model, so after the fit the engine derives one with the
same rule the stream services use (threshold = the largest inlier
distance among summary records); queries then flow through the shared
micro-batched read path of ``ServingFrontEnd``, giving every topology the
same ``QueryResult`` surface and latency accounting.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Iterator, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.config import PipelineConfig
from repro.checkpoint.manager import CheckpointManager
from repro.core.collective import sites_mesh
from repro.core.distributed import distributed_cluster, simulate_coordinator
from repro.kernels.pdist.ops import min_argmin
from repro.serve.scheduler import ScoreTicket, ServingScheduler, ShedReject
from repro.stream.service import (ModelState, PendingFit, QueryResult,
                                  ServiceConfig, ServingFrontEnd,
                                  StreamService)
from repro.stream.sharded import ShardedStreamService


class OneshotEngine(ServingFrontEnd):
    """Algorithm 3 behind the serving-front-end verb set.

    ``ingest`` accumulates raw rows; ``refresh`` runs the coordinator on
    everything accumulated (a pure function of the ingested points and the
    config seed — refreshing twice with no new data reproduces the same
    model bit for bit); the inherited read path serves queries.  The full
    coordinator result (outlier ids, summary ids, communication) stays
    available as ``.result``.
    """

    _topology = "oneshot"

    def __init__(self, pipeline: PipelineConfig):
        topo = pipeline.topology
        if topo.kind != "oneshot":
            raise ValueError(f"OneshotEngine needs topology.kind='oneshot', "
                             f"got {topo.kind!r}")
        p = pipeline.problem
        # ServingFrontEnd only needs the shared serving knobs; reusing the
        # stream dataclass keeps the read/checkpoint glue identical
        super().__init__(ServiceConfig(
            dim=p.dim, k=p.k, t=p.t, metric=p.metric,
            micro_batch=topo.micro_batch, second_iters=pipeline.second_iters,
            policy=pipeline.kernels, summarizer=pipeline.summarizer,
            seed=pipeline.seed))
        self.pipeline = pipeline
        self._rows: list[np.ndarray] = []
        self.result: Optional[dict] = None

    # ------------------------------------------------------------ write path
    def ingest(self, points, weights=None) -> None:
        self.poll_refresh()
        x, w = self._validate_points(points, weights)
        if w is not None:
            raise ValueError("oneshot topology clusters raw (unit-weight) "
                             "points; weighted records are a stream concept")
        self._rows.append(x)

    @property
    def total_ingested(self) -> int:
        return int(sum(r.shape[0] for r in self._rows))

    def _root_records(self) -> int:
        # the oneshot "root" is every raw row the coordinator will see
        return self.total_ingested

    # ------------------------------------------------------------ refresh fit
    def _fit_closure(self, version: int):
        if not self._rows:
            raise RuntimeError("refresh() before any point was ingested")
        x = np.concatenate(self._rows)
        self._rows = [x]          # compact the buffer while we have it
        # the coordinator takes host rows: nothing to upload ahead of it
        return PendingFit((), functools.partial(self._fit, x, version))

    def _fit(self, x: np.ndarray, version: int) -> ModelState:
        res = _run_oneshot(x, self.pipeline)
        self.result = res
        return _model_from_result(x, res, self.pipeline, version)

    # ------------------------------------------------------------ checkpoint
    def _result_arrays(self) -> dict:
        r = self.result or {}
        return {
            "summary_ids": np.asarray(
                r.get("summary_ids", np.zeros(0)), np.int64),
            "summary_weights": np.asarray(
                r.get("summary_weights", np.zeros(0)), np.float32),
            "outlier_ids": np.asarray(
                r.get("outlier_ids", np.zeros(0)), np.int64),
            "comm_records": np.float64(r.get("comm_records", 0.0)),
        }

    def save(self, manager: CheckpointManager, step: int, *,
             blocking: bool = True, extra_meta: Optional[dict] = None) -> None:
        self.join_refresh()
        x = (np.concatenate(self._rows) if self._rows
             else np.zeros((0, self.cfg.dim), np.float32))
        r = self.result
        n_sum = 0 if r is None else len(r["summary_ids"])
        n_out = 0 if r is None else len(r["outlier_ids"])
        state = {"x": x, "model": self._model_arrays(),
                 "result": self._result_arrays(),
                 "counters": {"next_id": np.int64(self._next_id)}}
        manager.save(step, state, blocking=blocking,
                     meta={**(extra_meta or {}),
                           "format": "oneshot-session-v1",
                           "n_rows": int(x.shape[0]),
                           "n_summary": n_sum, "n_outliers": n_out})

    @classmethod
    def restore(cls, pipeline: PipelineConfig, manager: CheckpointManager,
                step: int | None = None) -> "OneshotEngine":
        meta = manager.read_meta(step)
        fmt = meta.get("format")
        if fmt != "oneshot-session-v1":
            raise ValueError(
                f"checkpoint format {fmt!r} is not a oneshot session "
                f"checkpoint — restore it with the layer that wrote it")
        eng = cls(pipeline)
        n_sum, n_out = int(meta["n_summary"]), int(meta["n_outliers"])
        skel = {"x": np.zeros((int(meta["n_rows"]), pipeline.problem.dim),
                              np.float32),
                "model": eng._model_skeleton(eng.cfg),
                "result": {"summary_ids": np.zeros(n_sum, np.int64),
                           "summary_weights": np.zeros(n_sum, np.float32),
                           "outlier_ids": np.zeros(n_out, np.int64),
                           "comm_records": np.float64(0)},
                "counters": {"next_id": np.int64(0)}}
        state, _ = manager.restore(skel, step)
        x = np.asarray(state["x"], np.float32)
        eng._rows = [x] if x.shape[0] else []
        eng._next_id = int(state["counters"]["next_id"])
        eng._install_model_arrays(state["model"])
        if eng.model is not None:   # a fit happened: rebuild .result from
            r = state["result"]     # the persisted arrays + the model
            eng.result = {
                "centers": np.asarray(eng.model.centers),
                "outlier_ids": np.asarray(r["outlier_ids"]),
                "summary_ids": np.asarray(r["summary_ids"]),
                "summary_weights": np.asarray(r["summary_weights"]),
                "comm_records": float(r["comm_records"]),
                "cost": float(eng.model.cost),
            }
        return eng


def _run_oneshot(x: np.ndarray, pipeline: PipelineConfig) -> dict:
    """Drive the coordinator layer a direct caller would, same key."""
    p, topo = pipeline.problem, pipeline.topology
    s = topo.sites
    key = jax.random.key(pipeline.seed)
    common = dict(k=p.k, t=p.t, partition=topo.partition,
                  summarizer=pipeline.summarizer,
                  second_iters=pipeline.second_iters, metric=p.metric,
                  policy=pipeline.kernels)
    if not topo.use_shard_map:
        parts = np.array_split(x, s)
        res = simulate_coordinator(parts, key, **common)
        # both execution paths expose the same result keys (they are also
        # what the checkpoint persists, so .result survives Session.load)
        return {k: res[k] for k in ("centers", "outlier_ids", "summary_ids",
                                    "summary_weights", "comm_records",
                                    "cost")}
    if x.shape[0] % s:
        raise ValueError(
            f"topology.use_shard_map needs len(points) divisible by "
            f"sites={s}, got {x.shape[0]} rows; pad or drop the remainder")
    if len(jax.devices()) < s:
        raise RuntimeError(
            f"topology.use_shard_map needs >= {s} devices for "
            f"{s} sites, have {len(jax.devices())}; drop use_shard_map "
            f"to run host-simulated")
    res = distributed_cluster(
        jnp.asarray(x, jnp.float32).reshape(s, -1, x.shape[1]), key,
        sites_mesh(s), **common)
    out = np.asarray(res.outlier_ids)
    sid = np.asarray(res.summary_ids)
    keep = sid >= 0
    return {
        "centers": np.asarray(res.centers),
        "outlier_ids": out[out >= 0],
        "summary_ids": sid[keep],
        "summary_weights": np.asarray(res.summary_weights)[keep],
        "comm_records": float(res.comm_records),
        "cost": float(res.cost),
    }


def _model_from_result(x: np.ndarray, res: dict, pipeline: PipelineConfig,
                       version: int) -> ModelState:
    """Serving model from a coordinator result — same threshold rule as
    ``repro.stream.service.fit_model`` (largest inlier distance among the
    summary records the second level was fit on)."""
    p = pipeline.problem
    centers = jnp.asarray(res["centers"], jnp.float32)
    pts = jnp.asarray(x[res["summary_ids"]], jnp.float32)
    dist, _ = min_argmin(pts, centers, metric=p.metric,
                         policy=pipeline.kernels)
    inlier = ~np.isin(res["summary_ids"], res["outlier_ids"])
    dist = np.asarray(dist)
    threshold = float(dist[inlier].max()) if inlier.any() else 0.0
    return ModelState(
        centers=centers,
        threshold=jnp.float32(max(threshold, 1e-12)),
        cost=jnp.float32(res["cost"]),
        version=jnp.int32(version),
        trained_weight=jnp.float32(x.shape[0]))


class Session:
    """The one front door: construct from a :class:`PipelineConfig`, then
    ``fit`` / ``ingest`` / ``refresh`` / ``score`` / ``save`` regardless of
    topology.  ``session.engine`` exposes the underlying layer
    (``StreamService``, ``ShardedStreamService`` or ``OneshotEngine``) as
    the escape hatch for layer-specific surface."""

    def __init__(self, config: PipelineConfig, *, _engine=None):
        self.config = config
        self._serving: Optional[ServingScheduler] = None
        self._attach_lock = threading.Lock()
        if config.tracing is not None:
            # pin the process flight recorder to the artifact's knobs
            # (sampling, ring, seed) before the engine captures handles
            from repro import obs
            obs.apply_trace_spec(config.tracing)
        if _engine is not None:
            self.engine = _engine
        else:
            kind = config.topology.kind
            if kind == "stream":
                self.engine = StreamService(config.service_config())
            elif kind == "sharded":
                self.engine = ShardedStreamService(config.sharded_config())
            else:
                self.engine = OneshotEngine(config)

    # ------------------------------------------------------------ serving
    @property
    def serving(self) -> Optional[ServingScheduler]:
        """The attached async scheduler — None until the first
        :meth:`score_stream` call (or explicit :meth:`serve`)."""
        return self._serving

    def serve(self) -> ServingScheduler:
        """Attach (and return) the continuous-batching scheduler for this
        session's engine, configured by ``config.serving`` (defaults apply
        when the config has no serving section).  Idempotent; once a
        scheduler is attached, the synchronous verbs route through its
        ``engine_lock`` so direct ``score``/``refresh`` calls and worker
        ticks never interleave on the engine.  Safe to race: concurrent
        first callers attach exactly one scheduler."""
        if self._serving is None:
            with self._attach_lock:
                if self._serving is None:
                    self._serving = ServingScheduler(self.engine,
                                                     self.config.serving)
        return self._serving

    def score_stream(self, queries, *, tenant: str = "default",
                     timeout: Optional[float] = None,
                     ) -> Iterator[Union[QueryResult, ShedReject]]:
        """Score rows through the async serving path.

        Rows are admitted (and possibly shed) *now*, on the caller's
        thread — many threads calling ``score_stream`` concurrently share
        one scheduler, and their rows coalesce into common worker ticks.
        Returns an iterator yielding, per row in order, the engine's
        ``QueryResult`` or a typed :class:`ShedReject`; iterate to block
        on completion.  Scores are bit-identical to :meth:`score`.
        """
        tickets = self.serve().submit(queries, tenant=tenant)
        return (t.result(timeout) for t in tickets)

    def submit_stream(self, queries, *, tenant: str = "default",
                      ) -> "list[ScoreTicket]":
        """Like :meth:`score_stream` but returns the raw tickets, for
        callers that want ``done()`` polling or per-ticket latency."""
        return self.serve().submit(queries, tenant=tenant)

    def close(self) -> None:
        """Drain and stop the serving scheduler, if one is attached.
        The session's synchronous verbs keep working afterwards."""
        with self._attach_lock:
            serving, self._serving = self._serving, None
        if serving is not None:
            serving.close()

    def __enter__(self) -> "Session":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def _engine_guard(self):
        """The scheduler's engine lock when serving is attached (direct
        verbs must not interleave with worker ticks), else a no-op."""
        if self._serving is not None:
            return self._serving.engine_lock
        return contextlib.nullcontext()

    # ------------------------------------------------------------ verbs
    def ingest(self, points, weights=None, *, site: int | None = None) -> None:
        """Feed raw points.  ``site=`` pins a batch to one site (sharded
        topology only — elsewhere routing is not a concept)."""
        if site is not None:
            if self.config.topology.kind != "sharded":
                raise ValueError(
                    f"site= routing needs topology.kind='sharded', this "
                    f"session is {self.config.topology.kind!r}")
            with self._engine_guard():
                self.engine.ingest(points, weights, site=site)
        else:
            with self._engine_guard():
                self.engine.ingest(points, weights)

    def refresh(self, *, blocking: bool = True) -> Optional[ModelState]:
        """(Re)fit the serving model on everything ingested so far."""
        with self._engine_guard():
            return self.engine.refresh(blocking=blocking)

    def fit(self, points=None, weights=None) -> ModelState:
        """``ingest`` (optional) + blocking ``refresh`` in one call."""
        if points is not None:
            self.ingest(points, weights)
        with self._engine_guard():
            return self.engine.refresh(blocking=True)

    def score(self, queries) -> list:
        """Score query rows against the current model; returns the same
        ``QueryResult`` records every topology's read path produces."""
        with self._engine_guard():
            return self.engine.score(queries)

    def latency_stats(self) -> dict:
        return self.engine.latency_stats()

    def store_stats(self) -> Optional[dict]:
        """Aggregate tiered-store movement tallies across this session's
        trees — ``{"spills", "page_ins", "spill_bytes", "page_in_bytes"}``
        summed over sites — or None when the config has no tiered store
        (oneshot topology, no ``store`` section, or an untiered spec).
        Per-series detail lives in :meth:`stats` under ``store.*``."""
        trees = []
        if hasattr(self.engine, "tree"):
            trees = [self.engine.tree]
        elif hasattr(self.engine, "trees"):
            trees = list(self.engine.trees)
        stores = [t._store for t in trees if t._store is not None]
        if not stores:
            return None
        totals: dict = {}
        for s in stores:
            for k, v in s.stats().items():
                totals[k] = totals.get(k, 0) + v
        return totals

    def stats(self) -> dict:
        """The process metrics snapshot (``repro.obs``): one plain dict of
        every counter, gauge and latency/phase histogram the layers under
        this session reported — serve latency, ingest/refresh/score phase
        timings, tree activity, comm records+bytes per site, kernel-backend
        dispatch counts, checkpoint durations.  JSON-serializable as-is;
        render for Prometheus with ``repro.obs.render_prometheus``.

        The snapshot is process-wide by design (one registry, like any
        exporter) — two sessions of the same topology share series.
        """
        from repro import obs
        return obs.snapshot()

    def dump_trace(self, path, fmt: str = "chrome"):
        """Write the flight recorder's buffered spans to ``path``.

        ``fmt="chrome"`` (default) writes Chrome trace-event JSON — load
        it in Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``
        to see each request/refresh as one stitched timeline.
        ``fmt="jsonl"`` writes one JSON record per span/event.  Returns
        the path written.  The recorder is process-wide, like
        :meth:`stats`.
        """
        from repro import obs
        return obs.dump_trace(path, fmt=fmt)

    @property
    def last_fit(self):
        """:class:`repro.stream.service.FitStats` of the most recent
        installed refresh (duration, records folded) — None before the
        first fit.  Staleness is ``engine.seconds_since_install()``."""
        return self.engine.last_fit

    @property
    def model(self) -> Optional[ModelState]:
        return self.engine.model

    @property
    def result(self) -> Optional[dict]:
        """Oneshot coordinator detail (outlier/summary ids, comm records);
        None for stream topologies, whose model is the serving state."""
        return getattr(self.engine, "result", None)

    # ------------------------------------------------------------ persistence
    def save(self, directory, *, step: int | None = None,
             blocking: bool = True) -> int:
        """Checkpoint the full session under ``directory``.

        The serialized ``PipelineConfig`` is embedded in the checkpoint
        manifest, so :meth:`load` reconstructs topology and policies with
        no caller-side state.  Returns the step written."""
        manager = CheckpointManager(directory)
        if step is None:
            latest = manager.latest_step()
            step = (latest + 1) if latest is not None else 1
        with self._engine_guard():
            self.engine.save(
                manager, step, blocking=blocking,
                extra_meta={"pipeline_config": self.config.to_dict()})
        return step

    @classmethod
    def load(cls, directory, *, step: int | None = None) -> "Session":
        """Rebuild a session from a checkpoint alone: the manifest's
        embedded config selects the topology and policies, then the
        matching layer restores its state (post-restore scores are
        bit-identical to the saved session's)."""
        manager = CheckpointManager(directory)
        meta = manager.read_meta(step)
        cfg_dict = meta.get("pipeline_config")
        if cfg_dict is None:
            raise ValueError(
                f"checkpoint in {directory} has no embedded pipeline config "
                f"(was it written by Session.save?); restore it with the "
                f"layer-specific restore() it was written by")
        config = PipelineConfig.from_dict(cfg_dict)
        kind = config.topology.kind
        if kind == "stream":
            engine = StreamService.restore(config.service_config(),
                                           manager, step)
        elif kind == "sharded":
            engine = ShardedStreamService.restore(config.sharded_config(),
                                                  manager, step)
        else:
            engine = OneshotEngine.restore(config, manager, step)
        return cls(config, _engine=engine)
