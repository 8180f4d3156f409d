"""Multi-host sharded streaming service: per-site trees + all_gather roots.

Topology (Algorithm 3 lifted onto the stream):

    site 0: raw points --> leaf buffer --> StreamTree (merge-and-reduce)
    site 1: raw points --> leaf buffer --> StreamTree          |
      ...                                                      | packed roots
    site s: raw points --> leaf buffer --> StreamTree          v
                                       one all_gather of fixed-shape roots
                                                               |
                       replicated weighted k-means--  <--------+
                                   (one global ModelState on every site)

Each site ingests its shard of the stream completely locally — leaf
reduction, merge-and-reduce, window eviction never leave the site.  On the
refresh cadence every site contributes its tree root, padded to one static
record capacity, to a single ``all_gather`` (the paper's one round of
communication, reusing the collective path of ``repro.core.distributed``),
and the second-level weighted k-means-- runs replicated on the union.
Because the second level sees *every* site's summaries, a global outlier
that looks locally unremarkable — e.g. a small cluster split evenly over
all sites — is still caught, exactly as in the one-shot Algorithm 3.

Execution paths, same math:

* host-simulated (default, any device count): the driver owns all ``s``
  trees, the gather is a concatenation in site order — bit-identical to
  what the collective delivers — and communication is *accounted* (records
  and bytes) rather than performed;
* ``use_shard_map=True``: the gather + second level run as one
  ``shard_map`` program over the ``sites`` mesh axis
  (``repro.core.collective``), so on hardware the root exchange lowers to
  one ICI collective per leaf of the payload.  It needs >= s devices and
  raises at construction otherwise — it never quietly runs host-sim.

The read path (micro-batched scoring, latency accounting) and the
double-buffered async refresh are inherited from
``repro.stream.service.ServingFrontEnd``: queries keep scoring against the
previous model while the gathered refresh computes.

Communication cost per refresh is exactly the packed roots: s sites x
root_rows records x (4d + 4 + 1) bytes — reported per refresh in
``last_refresh`` and aggregated by ``benchmarks/stream_bench.py --sites``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint.manager import CheckpointManager
from repro.core.collective import (gather_sites, gathered_bytes,
                                   payload_bytes, replicated_coordinator,
                                   sites_mesh)
from repro.core.distributed import local_budget
from repro.stream.service import (BaseServiceConfig, ModelState,
                                  ServingFrontEnd, fit_model)
from repro.stream.tree import StreamTree, TreeConfig
from repro.stream.weighted import _bucket


@dataclasses.dataclass(frozen=True)
class ShardedServiceConfig(BaseServiceConfig):
    """``BaseServiceConfig`` (all serving knobs, incl. ``refresh_every`` and
    ``window`` which are GLOBAL raw-point counts here) plus the multi-host
    topology fields only the sharded service has."""

    n_sites: int = 4
    site_budget: str = "full"        # "full": t per site (window/adversarial
    #                                  safe); "paper": 2t/s (cheaper roots)
    use_shard_map: bool = False      # real collective (needs >= n_sites devices)

    def site_t(self) -> int:
        if self.site_budget == "full":
            return self.t
        if self.site_budget == "paper":
            return local_budget(self.t, self.n_sites, "random")
        raise ValueError(f"unknown site_budget {self.site_budget!r}")

    def site_tree_config(self) -> TreeConfig:
        w = self.window
        if w is not None:
            # each site sees ~1/s of the stream, so a site-local window of
            # ceil(W/s) tracks the last ~W global points
            w = -(-w // self.n_sites)
        return TreeConfig(
            dim=self.dim, k=self.k, t=self.site_t(),
            leaf_size=self.leaf_size, metric=self.metric,
            policy=self.policy, summarizer=self.summarizer, window=w,
            seed=self.seed, store=self.store)


class RefreshStats(NamedTuple):
    """Communication accounting for one gathered refresh."""
    version: int
    path: str                 # "shard_map" | "host-sim"
    root_rows: int            # static per-site packed-root rows
    per_site_records: tuple   # live (valid) records each site contributed
    comm_records: int         # total valid records gathered (paper's measure)
    comm_bytes: int           # total bytes one all_gather moves (padded)
    payload_bytes: int        # one site's padded contribution in bytes


class ShardedStreamService(ServingFrontEnd):
    """One ``StreamTree`` per site; one ``all_gather`` of roots per refresh.

    The driver process owns every site's tree (host-simulated sites); on a
    real deployment each host would run the write path for its own site and
    the identical replicated refresh — the state layout (per-site subtrees
    keyed by site id) and the fixed-shape root exchange are the same either
    way, which is what makes the host-sim path a faithful model of the
    multi-host one.
    """

    _topology = "sharded"

    def __init__(self, cfg: ShardedServiceConfig,
                 key: jax.Array | None = None):
        if cfg.n_sites < 1:
            raise ValueError(f"n_sites must be >= 1, got {cfg.n_sites}")
        if cfg.use_shard_map and len(jax.devices()) < cfg.n_sites:
            raise RuntimeError(
                f"use_shard_map needs >= {cfg.n_sites} devices for "
                f"{cfg.n_sites} sites, have {len(jax.devices())}; drop "
                f"use_shard_map to run host-simulated")
        super().__init__(cfg)
        key = key if key is not None else jax.random.key(cfg.seed)
        kt, self._model_key = jax.random.split(key)
        site_cfg = cfg.site_tree_config()
        self.trees = [StreamTree(site_cfg, jax.random.fold_in(kt, i))
                      for i in range(cfg.n_sites)]
        for i, tr in enumerate(self.trees):
            tr.obs_labels["site"] = i
        self._routed = 0             # round-robin cursor over sites
        self._fit_program = None     # cached shard_map program (all refreshes)
        self.last_refresh: Optional[RefreshStats] = None

    def _root_records(self) -> int:
        return self.num_records

    # ------------------------------------------------------------ write path
    def ingest(self, points, weights=None, site: int | None = None) -> None:
        """Feed raw points.

        ``site=None`` (dispatcher model): rows are interleaved round-robin
        over sites, continuing across calls, so every site sees an unbiased
        1/s sample of the stream.  ``site=i`` pins the whole batch to site i
        — the multi-host reality, where each host ingests only the traffic
        that reached it.
        """
        self.poll_refresh()
        cfg = self.cfg
        x, w = self._validate_points(points, weights)
        if site is not None:
            if not 0 <= site < cfg.n_sites:
                raise ValueError(
                    f"site {site} out of range [0, {cfg.n_sites})")
            sink = self.trees[site].ingest
        else:
            def sink(xc, wc):
                lanes = (self._routed + np.arange(xc.shape[0])) % cfg.n_sites
                for j in range(cfg.n_sites):
                    m = lanes == j
                    if m.any():
                        self.trees[j].ingest(xc[m],
                                             None if wc is None else wc[m])
                self._routed += xc.shape[0]
        self._ingest_cadenced(x, w, sink)

    # ------------------------------------------------------------ refresh fit
    def _gathered_program(self):
        """One shard_map program for every refresh: key/version flow in as
        arguments so the traced closure is stable and the compiled program
        is reused (it only recompiles when the packed-root rows grow)."""
        if self._fit_program is None:
            cfg = self.cfg

            def per_site(triple, key, version):
                p, w, v = triple   # each carries its site block: (1, rows, ..)
                gp, gw, gv = gather_sites((p[0], w[0], v[0]))
                return fit_model(gp, gw, gv, key, version, k=cfg.k, t=cfg.t,
                                 iters=cfg.second_iters, metric=cfg.metric,
                                 policy=cfg.policy)

            self._fit_program = replicated_coordinator(
                per_site, sites_mesh(cfg.n_sites), n_sharded=1)
        return self._fit_program

    def _fit_closure(self, version: int):
        """Snapshot every site's packed root now; gather + fit later.

        With ``cfg.store`` set the fit key derives from the per-site root
        epochs (monotone, so the tuple repeats iff no site's root moved):
        an unchanged gathered root refits bit-identically, licensing the
        incremental-refresh skip.  The opt-in warm start is host-sim only —
        threading previous centers through the cached shard_map program
        would retrace it for every refresh.
        """
        cfg = self.cfg
        recs = [tr.num_records for tr in self.trees]
        if sum(recs) == 0:
            raise RuntimeError("refresh() before any point was ingested")
        store, init = cfg.store, None
        epochs = tuple(tr.root_epoch for tr in self.trees)
        if store is not None:
            # touch the incremental-refresh series so a store-configured
            # run always exposes them (at zero until the first skip)
            obs.counter("refresh.skipped", topology=self._topology).inc(0)
            obs.counter("refresh.warm_starts",
                        topology=self._topology).inc(0)
            if (store.incremental_refresh and self.model is not None
                    and epochs == self._last_fit_epoch):
                return None
            self._pending_fit_epoch = epochs
        # one static row count for every site: the all_gather payload shape
        rows = _bucket(max(max(recs), 1))
        # per-site gather spans: inside refresh.gather, so one refresh
        # trace stitches every site's root snapshot under a single root
        roots = []
        for i, tr in enumerate(self.trees):
            with obs.trace("refresh.site_root", topology="sharded", site=i):
                roots.append(tr.packed_root(rows))
        pts = np.stack([r[0] for r in roots])          # (s, rows, d)
        wts = np.stack([r[1] for r in roots])          # (s, rows)
        val = np.stack([r[2] for r in roots])          # (s, rows)
        one_site = (roots[0][0], roots[0][1], roots[0][2])
        use_sm = cfg.use_shard_map
        site_bytes = payload_bytes(one_site)
        self.last_refresh = RefreshStats(
            version=version,
            path="shard_map" if use_sm else "host-sim",
            root_rows=rows,
            per_site_records=tuple(recs),
            comm_records=int(sum(recs)),
            comm_bytes=gathered_bytes(one_site, cfg.n_sites),
            payload_bytes=site_bytes)
        # every site ships the same padded root shape, hence equal bytes
        obs.record_comm(recs, [site_bytes] * cfg.n_sites, topology="sharded")
        if store is not None:
            # epoch-keyed: the same roots refit to the same model.  The sum
            # is strictly monotone in the per-site epochs, so it collides
            # only when every site's root is unchanged.
            key = jax.random.fold_in(self._model_key, sum(epochs))
            if (store.warm_start_frac > 0.0 and self.model is not None
                    and self._last_fit_epoch is not None and not use_sm):
                parts = [tr.changed_weight_since(e) for tr, e
                         in zip(self.trees, self._last_fit_epoch)]
                changed = sum(c for c, _ in parts)
                total = sum(t_ for _, t_ in parts)
                if changed <= store.warm_start_frac * total:
                    init = self.model.centers
                    obs.counter("refresh.warm_starts",
                                topology=self._topology).inc()
        else:
            key = jax.random.fold_in(self._model_key, version)

        if not use_sm:
            # host-sim: concatenation in site order is exactly what the
            # collective would deliver to every participant
            s, r, d = pts.shape
            return functools.partial(
                fit_model, jnp.asarray(pts.reshape(s * r, d)),
                jnp.asarray(wts.reshape(s * r)),
                jnp.asarray(val.reshape(s * r)), key, version, k=cfg.k,
                t=cfg.t, iters=cfg.second_iters, metric=cfg.metric,
                policy=cfg.policy, init_centers=init)

        program = self._gathered_program()
        triple = (jnp.asarray(pts), jnp.asarray(wts), jnp.asarray(val))
        return lambda: program(triple, key, np.int32(version))

    # ------------------------------------------------------------ aggregates
    @property
    def num_records(self) -> int:
        return sum(tr.num_records for tr in self.trees)

    @property
    def total_weight(self) -> float:
        return float(sum(tr.total_weight for tr in self.trees))

    @property
    def total_ingested(self) -> int:
        return sum(tr.total_ingested for tr in self.trees)

    # ------------------------------------------------------------ checkpoint
    def _state(self) -> dict:
        self.join_refresh()
        return {
            "sites": {f"site_{i:03d}": tr.pack_state()
                      for i, tr in enumerate(self.trees)},
            "model": self._model_arrays(),
            "counters": {
                "since_refresh": np.int64(self._since_refresh),
                "next_id": np.int64(self._next_id),
                "routed": np.int64(self._routed),
                "last_fit_epochs": (
                    np.full((self.cfg.n_sites,), -1, np.int64)
                    if self._last_fit_epoch is None
                    else np.asarray(self._last_fit_epoch, np.int64)),
                "model_key": np.asarray(jax.random.key_data(self._model_key)),
            },
        }

    def _skeleton(self) -> dict:
        cfg = self.cfg
        site_cfg = cfg.site_tree_config()
        return {
            "sites": {f"site_{i:03d}": StreamTree.skeleton_state(site_cfg)
                      for i in range(cfg.n_sites)},
            "model": self._model_skeleton(cfg),
            "counters": {"since_refresh": np.int64(0), "next_id": np.int64(0),
                         "routed": np.int64(0),
                         "last_fit_epochs": np.full((cfg.n_sites,), -1,
                                                    np.int64),
                         "model_key": np.zeros((2,), np.uint32)},
        }

    def save(self, manager: CheckpointManager, step: int, *,
             blocking: bool = True, extra_meta: Optional[dict] = None) -> None:
        """``extra_meta``: caller facts merged into the manifest meta (the
        ``Session`` facade embeds its serialized ``PipelineConfig`` here)."""
        manager.save(step, self._state(), blocking=blocking,
                     meta={**(extra_meta or {}),
                           "format": "sharded-stream-v1",
                           "n_sites": self.cfg.n_sites})

    @classmethod
    def restore(cls, cfg: ShardedServiceConfig, manager: CheckpointManager,
                step: int | None = None) -> "ShardedStreamService":
        meta = manager.read_meta(step)
        fmt = meta.get("format")
        if fmt is not None and fmt != "sharded-stream-v1":
            raise ValueError(
                f"checkpoint format {fmt!r} is not a sharded stream "
                f"checkpoint — restore it with the service that wrote it")
        ck_sites = meta.get("n_sites")
        if ck_sites is not None and ck_sites != cfg.n_sites:
            raise ValueError(
                f"checkpoint was written by {ck_sites} sites but the "
                f"restoring config has n_sites={cfg.n_sites}; per-site trees "
                f"cannot be re-sharded — restore with the writer's topology")
        svc = cls(cfg)
        state, _ = manager.restore(svc._skeleton(), step)
        site_cfg = cfg.site_tree_config()
        svc.trees = [
            StreamTree.from_state(site_cfg, state["sites"][f"site_{i:03d}"])
            for i in range(cfg.n_sites)]
        for i, tr in enumerate(svc.trees):
            tr.obs_labels["site"] = i
        svc._since_refresh = int(state["counters"]["since_refresh"])
        svc._next_id = int(state["counters"]["next_id"])
        svc._routed = int(state["counters"]["routed"])
        lfe = np.asarray(state["counters"]["last_fit_epochs"])
        svc._last_fit_epoch = (tuple(int(e) for e in lfe)
                               if (lfe >= 0).all() else None)
        svc._model_key = jax.random.wrap_key_data(
            jnp.asarray(state["counters"]["model_key"], jnp.uint32))
        svc._install_model_arrays(state["model"])
        return svc
