"""Merge-and-reduce buffer tree over weighted summaries.

Ingest path: raw points accumulate in a leaf buffer; every ``leaf_size``
points the buffer is reduced to a level-0 weighted summary (by default
Algorithm 1 at full outlier budget t; ``TreeConfig.summarizer`` selects
any registered ``repro.summarize`` algorithm for both the leaf reduction
and the merge-reduce step).  Whenever two summaries share a level, the
older pair is merged (concatenate) and reduced (the summarizer re-run on
the union) into one level-(l+1) summary — the classic binary-counter
coreset tree, so a stream of n points holds at most O(log(n / leaf_size))
live summaries of O(m + 8t) records each: O(m log n) memory total.
Mass conservation — the summarize-registry contract — is exactly what
makes any registered summarizer safe to slot in here.

Sliding window (optional): with ``window=W`` set, merges are capped so no
summary spans more than max(leaf_size, W // 4) raw points, and summaries
whose newest point has fallen out of the window are evicted whole.  The
model then tracks the last ~W points with eviction granularity <= W/4.

Tiered storage (optional): with ``TreeConfig.store`` set to a tiered
:class:`repro.store.StoreSpec`, summaries beyond the hot budget spill to
disk through :class:`repro.store.TieredStore` and are demand-paged back
exactly when a merge, ``root()`` or ``pack_state()`` touches them — the
root stays bit-identical to the all-resident tree, only residency moves.
The tree also tracks a monotone ``root_epoch`` (bumped on every mutation
that changes ``root()``) plus per-node creation epochs, which is what
lets the serving layer skip or warm-start provably-redundant refreshes.

Device mirrors (optional): a tree given a ``device`` keeps each node's
summary on that device from the first refresh that needs it until the
node is evicted, merged away or spilled — a summary never changes once
made — and ``device_root()`` assembles the refresh root there, bit for
bit ``packed_root()``: a refresh uploads only the nodes made since the
last one.  Paged-in nodes and the leaf buffer are uploaded transiently.

Checkpointing: the tree's state packs into a *fixed-shape* pytree of
arrays (``pack_state``/``from_state``), so ``CheckpointManager`` can
save/restore it across process restarts with its usual shape-checked
manifest — no pickling.  Spilled summaries are paged in for the pack (a
checkpoint is self-contained) and the restored tree re-applies its hot
budget.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.kernels.dispatch import KernelPolicy, get_default_policy
from repro.store.spec import StoreSpec
from repro.stream.weighted import WeightedSummary, _bucket

if TYPE_CHECKING:   # runtime import is lazy: repro.store.tiered imports
    from repro.store.tiered import TieredStore   # this module's package
from repro.summarize.base import (SummarizerPolicy, get_default_summarizer,
                                  record_bound, reduce_summaries, summarize)


@dataclasses.dataclass(frozen=True)
class TreeConfig:
    dim: int
    k: int
    t: int
    leaf_size: int = 2048
    alpha: float = 2.0
    beta: float = 0.45
    metric: str = "l2sq"
    # None = capture the process default (set_default_policy) at construction
    policy: Optional[KernelPolicy] = None
    # None = capture the process default (set_default_summarizer); the
    # default "auto" resolves to the paper summarizer — bit-identical to
    # the pre-registry weighted_summary_outliers/resummarize calls
    summarizer: Optional[SummarizerPolicy] = None
    window: Optional[int] = None     # raw points; None = full stream
    max_summaries: int = 64          # checkpoint slots; force-merge beyond
    max_points: int = 2 ** 34        # stream-length bound for the record cap
    seed: int = 0
    # None = everything resident (the classic in-memory tree); a tiered
    # StoreSpec spills cold levels to disk behind the same root
    store: Optional[StoreSpec] = None

    def __post_init__(self):
        if self.policy is None:
            object.__setattr__(self, "policy", get_default_policy())
        if self.summarizer is None:
            object.__setattr__(self, "summarizer", get_default_summarizer())


def record_cap(cfg: TreeConfig) -> int:
    """Static per-summary record capacity for checkpoint packing.

    Delegates to the selected summarizer's registered ``record_bound`` —
    for the paper summarizer: centers <= rounds * m where rounds depends
    only on the mass (<= the mass bound below) and candidates carry >= 1
    mass each in tree use (raw points enter with unit weight), so <= 8t.

    With a sliding window the mass bound tightens: no summary can carry
    more mass than the live stream, which eviction keeps under
    ``window + merge-span + flush slack`` (unit weights).  The force-merge
    loop in ``_compact`` ignores the span cap, so the tightening only
    applies when the checkpoint slot budget provably keeps force-merge
    from firing (every node carries >= leaf_size mass, so the node count
    never exceeds live_mass // leaf_size).  Non-windowed configs keep the
    ``cfg.max_points`` stream-length bound unchanged.
    """
    max_points = cfg.max_points
    if cfg.window is not None:
        span = max(cfg.leaf_size, cfg.window // 4)
        live = cfg.window + span + 2 * cfg.leaf_size
        if live // cfg.leaf_size + 1 <= cfg.max_summaries:
            max_points = min(max_points, live)
    return record_bound(cfg.summarizer, metric=cfg.metric, k=cfg.k, t=cfg.t,
                        alpha=cfg.alpha, beta=cfg.beta,
                        max_points=max_points, leaf_size=cfg.leaf_size)


@dataclasses.dataclass
class TreeNode:
    summary: Optional[WeightedSummary]   # None while spilled to the store
    level: int
    min_seq: int    # [min_seq, max_seq): raw-point sequence ids spanned
    max_seq: int
    count: int      # raw points spanned
    # metadata that must survive a spill (the store rebuilds the summary
    # from these + the on-disk blob) and feed refresh reuse decisions
    epoch: int = 0           # tree root_epoch when this node was created
    n_records: int = 0       # summary rows (== summary.points.shape[0])
    nbytes: int = 0          # resident payload bytes of the summary
    weight: float = 0.0      # summary mass (WeightedSummary.total_weight)
    spill_step: Optional[int] = None   # store step id while spilled
    # (points (b, d), weights (b,)) on the tree's device, zero-padded to
    # b = _bucket(n_records); None until a refresh uploads the node, and
    # again once it is evicted, merged away or spilled
    mirror: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _place(points, weights, m_points, m_weights, start, n):
    """Write rows [0, n) of a zero-padded mirror at row ``start`` of the
    root being assembled, in place.  The write covers the mirror's whole
    span, moved left where it would run past the root's end and rolled to
    match, and keeps what is there outside the node's n rows: one program
    per (root rows, mirror span) pair, and no buffer beyond the root."""
    span = m_points.shape[0]
    at = jnp.minimum(start, points.shape[0] - span)
    shift = start - at
    i = jnp.arange(span)
    keep = (i >= shift) & (i < shift + n)
    p = jnp.where(keep[:, None], jnp.roll(m_points, shift, axis=0),
                  lax.dynamic_slice_in_dim(points, at, span))
    w = jnp.where(keep, jnp.roll(m_weights, shift),
                  lax.dynamic_slice_in_dim(weights, at, span))
    return (lax.dynamic_update_slice_in_dim(points, p, at, 0),
            lax.dynamic_update_slice_in_dim(weights, w, at, 0))


class StreamTree:
    """Mergeable summary tree; state numpy-side, distance loops jitted.

    ``device``: where ``device_root`` keeps the nodes' mirrors; None (the
    default) mirrors nothing."""

    def __init__(self, cfg: TreeConfig, key: jax.Array | None = None, *,
                 device: Optional[jax.Device] = None):
        self.cfg = cfg
        self.device = device
        self._placed_rows: set[int] = set()  # root sizes device_root warmed
        self.key = key if key is not None else jax.random.key(cfg.seed)
        self.nodes: List[TreeNode] = []      # chronological order
        self._buf = np.zeros((cfg.leaf_size, cfg.dim), np.float32)
        self._buf_w = np.zeros((cfg.leaf_size,), np.float32)
        self._buf_n = 0
        self._flushed = 0                    # raw points reduced into leaves
        self.total_ingested = 0
        self._cap = record_cap(cfg)
        self._epoch = 0                      # bumped whenever root() changes
        # the spill tier is created lazily, on the first budget enforcement:
        # skeleton/throwaway trees never touch disk
        self._store: Optional[TieredStore] = None
        # telemetry labels; owners may add context after construction (the
        # sharded service tags each site's tree with its site id)
        self.obs_labels: dict = {"summarizer": cfg.summarizer.name}

    # ------------------------------------------------------------ ingest
    def ingest(self, points, weights=None) -> None:
        x = np.asarray(points, np.float32)
        if x.ndim == 1:
            x = x[None, :]
        if x.shape[1] != self.cfg.dim:
            raise ValueError(f"expected dim {self.cfg.dim}, got {x.shape[1]}")
        w = (np.ones((x.shape[0],), np.float32) if weights is None
             else np.asarray(weights, np.float32).reshape(-1))
        if w.shape[0] != x.shape[0]:
            raise ValueError(
                f"{w.shape[0]} weights for {x.shape[0]} points — a silent "
                f"truncation here would break mass conservation")
        if x.shape[0]:
            self._epoch += 1   # buffered rows are part of root()
        i = 0
        while i < x.shape[0]:
            take = min(self.cfg.leaf_size - self._buf_n, x.shape[0] - i)
            self._buf[self._buf_n:self._buf_n + take] = x[i:i + take]
            self._buf_w[self._buf_n:self._buf_n + take] = w[i:i + take]
            self._buf_n += take
            self.total_ingested += take
            i += take
            if self._buf_n == self.cfg.leaf_size:
                self._flush_leaf()

    def _next_key(self) -> jax.Array:
        self.key, sk = jax.random.split(self.key)
        return sk

    def _flush_leaf(self) -> None:
        cfg = self.cfg
        with obs.trace("ingest.leaf_flush", **self.obs_labels):
            summ = summarize(
                self._buf[:self._buf_n], self._buf_w[:self._buf_n],
                self._next_key(), k=cfg.k, t=cfg.t, alpha=cfg.alpha,
                beta=cfg.beta, metric=cfg.metric, policy=cfg.summarizer,
                kernel_policy=cfg.policy)
        obs.counter("tree.leaf_flushes", **self.obs_labels).inc()
        self._check_cap(summ)
        self._epoch += 1
        self.nodes.append(self._make_node(
            summ, level=0, min_seq=self._flushed,
            max_seq=self._flushed + self._buf_n, count=self._buf_n))
        self._flushed += self._buf_n
        self._buf_n = 0
        self._evict()
        self._compact()
        self._enforce_store()
        self._update_gauges()

    def _update_gauges(self) -> None:
        reg = obs.get_default_registry()
        if not reg.enabled:
            return
        reg.gauge("tree.records", **self.obs_labels).set(self.num_records)
        reg.gauge("tree.summaries", **self.obs_labels).set(len(self.nodes))
        reg.gauge("tree.max_level", **self.obs_labels).set(
            max((nd.level for nd in self.nodes), default=0))

    def _check_cap(self, summ: WeightedSummary) -> None:
        if summ.points.shape[0] > self._cap:
            raise RuntimeError(
                f"summary has {summ.points.shape[0]} records > static cap "
                f"{self._cap}; raise TreeConfig.max_points or check weights "
                f"(sub-unit weights break the 8t candidate-count bound)")

    # ------------------------------------------------------------ store
    def _make_node(self, summ: WeightedSummary, *, level: int, min_seq: int,
                   max_seq: int, count: int) -> TreeNode:
        from repro.store.tiered import summary_nbytes
        return TreeNode(
            summary=summ, level=level, min_seq=min_seq, max_seq=max_seq,
            count=count, epoch=self._epoch,
            n_records=int(summ.points.shape[0]),
            nbytes=summary_nbytes(summ),
            weight=float(summ.total_weight))

    @property
    def store(self) -> Optional[TieredStore]:
        """The spill tier, created on first use (None until then, and
        forever when the config has no tiered store)."""
        cfg = self.cfg
        if self._store is None and cfg.store is not None and cfg.store.tiered:
            from repro.store.tiered import TieredStore
            self._store = TieredStore(cfg.store, dim=cfg.dim,
                                      labels=self.obs_labels)
        return self._store

    def _enforce_store(self) -> None:
        if self.cfg.store is not None and self.cfg.store.tiered:
            self.store.enforce(self.nodes)
            for nd in self.nodes:
                if nd.summary is None:   # spilled: off the device as well
                    nd.mirror = None

    def _node_summary(self, nd: TreeNode) -> WeightedSummary:
        """The node's summary, demand-paged from the spill tier if cold
        (transient — the node stays cold; see TieredStore.page_in)."""
        if nd.summary is not None:
            return nd.summary
        return self._store.page_in(nd)

    def _discard_node(self, nd: TreeNode) -> None:
        nd.mirror = None
        if nd.spill_step is not None:
            self._store.discard(nd)

    @property
    def root_epoch(self) -> int:
        """Monotone counter, bumped on every mutation that changes
        ``root()`` (ingest, flush, merge, evict).  Equal epochs imply an
        identical root, which is what licenses skipping a refresh."""
        return self._epoch

    def level_epochs(self) -> dict[int, int]:
        """Per-level dirty epoch: the newest node-creation epoch at each
        live level (diagnostics for the incremental-refresh decisions)."""
        out: dict[int, int] = {}
        for nd in self.nodes:
            out[nd.level] = max(out.get(nd.level, 0), nd.epoch)
        return out

    def changed_weight_since(self, epoch: int) -> tuple[float, float]:
        """(mass created after ``epoch``, total live mass) — from node
        metadata + the buffer, no page-ins.  The serving layer compares
        the ratio against ``StoreSpec.warm_start_frac``."""
        buf = float(self._buf_w[:self._buf_n].sum()) if self._buf_n else 0.0
        changed = buf + sum(nd.weight for nd in self.nodes
                            if nd.epoch > epoch)
        total = buf + sum(nd.weight for nd in self.nodes)
        return changed, total

    # ------------------------------------------------------------ merge
    def _evict(self) -> None:
        if self.cfg.window is None:
            return
        cutoff = self.total_ingested - self.cfg.window
        keep = [nd for nd in self.nodes if nd.max_seq > cutoff]
        if len(keep) < len(self.nodes):
            obs.counter("tree.evictions",
                        **self.obs_labels).inc(len(self.nodes) - len(keep))
            self._epoch += 1
            for nd in self.nodes:
                if nd.max_seq <= cutoff:
                    self._discard_node(nd)   # spilled blob leaves with it
        self.nodes = keep

    def _merge_pair(self, i: int, j: int) -> None:
        a, b = self.nodes[i], self.nodes[j]
        cfg = self.cfg
        with obs.trace("ingest.merge_reduce", **self.obs_labels):
            # demand-page spilled operands exactly here, where the merge
            # actually consumes them
            summ = reduce_summaries(
                [self._node_summary(a), self._node_summary(b)],
                self._next_key(), k=cfg.k, t=cfg.t,
                alpha=cfg.alpha, beta=cfg.beta, metric=cfg.metric,
                policy=cfg.summarizer, kernel_policy=cfg.policy)
        obs.counter("tree.merges", **self.obs_labels).inc()
        self._check_cap(summ)
        self._epoch += 1
        self.nodes[i] = self._make_node(
            summ, level=max(a.level, b.level) + 1,
            min_seq=min(a.min_seq, b.min_seq),
            max_seq=max(a.max_seq, b.max_seq),
            count=a.count + b.count)
        del self.nodes[j]
        self._discard_node(a)
        self._discard_node(b)

    def _max_span(self) -> Optional[int]:
        if self.cfg.window is None:
            return None
        return max(self.cfg.leaf_size, self.cfg.window // 4)

    def _compact(self) -> None:
        span = self._max_span()
        while True:
            by_level: dict[int, list[int]] = {}
            for i, nd in enumerate(self.nodes):
                by_level.setdefault(nd.level, []).append(i)
            pair = None
            for lvl in sorted(by_level):
                ids = by_level[lvl]
                if len(ids) < 2:
                    continue
                i, j = ids[0], ids[1]   # oldest two of this level
                if span is not None and \
                        self.nodes[i].count + self.nodes[j].count > span:
                    continue
                pair = (i, j)
                break
            if pair is None:
                break
            self._merge_pair(*pair)
        # checkpoint slots are finite: collapse the two oldest summaries
        # regardless of level rather than overflow.
        while len(self.nodes) > self.cfg.max_summaries:
            self._merge_pair(0, 1)

    # ------------------------------------------------------------ read
    def root(self, include_buffer: bool = True):
        """Union of all live summaries (+ the unreduced buffer as unit-ish
        weighted raw records): (points (s,d), weights (s,), is_candidate).
        Spilled summaries are paged in transiently — the concatenation is
        bit-identical to the all-resident tree's."""
        summs = [self._node_summary(nd) for nd in self.nodes]
        pts = [s.points for s in summs]
        wts = [s.weights for s in summs]
        cand = [s.is_candidate for s in summs]
        if include_buffer and self._buf_n:
            pts.append(self._buf[:self._buf_n].copy())
            wts.append(self._buf_w[:self._buf_n].copy())
            cand.append(np.zeros((self._buf_n,), bool))
        if not pts:
            return (np.zeros((0, self.cfg.dim), np.float32),
                    np.zeros((0,), np.float32), np.zeros((0,), bool))
        return (np.concatenate(pts), np.concatenate(wts),
                np.concatenate(cand))

    def packed_root(self, rows: int | None = None,
                    include_buffer: bool = True):
        """``root()`` padded to a static row count for collectives.

        Returns ``(points (rows, d) f32, weights (rows,) f32,
        valid (rows,) bool)`` with zero rows / zero weight / False beyond the
        live records — exactly the (points, weights, valid) triple the
        second-level ``kmeans_minus_minus`` consumes, and a fixed shape every
        site can contribute to one ``all_gather``.  ``rows`` defaults to the
        shared power-of-two bucket of the live record count (the same
        bucketing the scoring path uses, so shapes — and therefore compiled
        programs — are reused across refreshes).
        """
        pts, wts, _ = self.root(include_buffer)
        s = pts.shape[0]
        rows = _bucket(max(s, 1)) if rows is None else rows
        if s > rows:
            raise ValueError(f"{s} live records exceed packed capacity {rows}")
        out_p = np.zeros((rows, self.cfg.dim), np.float32)
        out_w = np.zeros((rows,), np.float32)
        out_v = np.zeros((rows,), bool)
        out_p[:s] = pts
        out_w[:s] = wts
        out_v[:s] = True
        return out_p, out_w, out_v

    def device_root(self) -> tuple[tuple[jax.Array, jax.Array, jax.Array],
                                   int]:
        """``packed_root()`` assembled on ``self.device`` from the nodes'
        mirrors: the same rows in the same order, bit for bit.

        A node without a mirror is uploaded here, and its mirror kept while
        its summary is resident; a paged-in node and the leaf buffer are
        uploaded for this call only.  Returns ``((points, weights, valid),
        live rows uploaded)``.  The assembly is one placement per node into
        a zeroed root; the placements for every mirror span the tree can
        make are compiled the first time it meets that root size.
        """
        if self.device is None:
            raise RuntimeError("device_root() on a tree given no device")
        d, s = self.cfg.dim, self.num_records
        rows = _bucket(max(s, 1))
        points = jnp.zeros((rows, d), jnp.float32, device=self.device)
        weights = jnp.zeros((rows,), jnp.float32, device=self.device)
        if rows not in self._placed_rows:
            self._placed_rows.add(rows)
            # a node holds at most _cap records, the buffer < leaf_size
            top = min(rows, _bucket(max(self._cap, self.cfg.leaf_size)))
            span = _bucket(1)
            while span <= top:
                points, weights = _place(
                    points, weights,
                    jnp.zeros((span, d), jnp.float32, device=self.device),
                    jnp.zeros((span,), jnp.float32, device=self.device),
                    0, 0)
                span <<= 1
        start = uploaded = 0
        for nd in self.nodes:
            mirror = nd.mirror
            if mirror is None:
                summ = self._node_summary(nd)
                mirror = self._upload(summ.points, summ.weights)
                uploaded += nd.n_records
                if nd.summary is not None:   # a page-in stays transient
                    nd.mirror = mirror
            points, weights = _place(points, weights, *mirror, start,
                                     nd.n_records)
            start += nd.n_records
        if self._buf_n:   # copied: later ingest overwrites the buffer
            n = self._buf_n
            points, weights = _place(
                points, weights, *self._upload(self._buf[:n].copy(),
                                               self._buf_w[:n].copy()),
                start, n)
            uploaded += n
        valid = jnp.arange(rows, device=self.device) < s
        return (points, weights, valid), uploaded

    def _upload(self, points: np.ndarray, weights: np.ndarray):
        """(points, weights) zero-padded to ``_bucket`` rows, on the device.
        Arrays already that long are sent as they are: callers hand over
        arrays that nothing overwrites."""
        pad = _bucket(points.shape[0]) - points.shape[0]
        if pad:
            points = np.concatenate(
                [points, np.zeros((pad, self.cfg.dim), np.float32)])
            weights = np.concatenate([weights, np.zeros((pad,), np.float32)])
        return (jax.device_put(np.asarray(points, np.float32), self.device),
                jax.device_put(np.asarray(weights, np.float32), self.device))

    @property
    def total_weight(self) -> float:
        _, w, _ = self.root()
        return float(w.sum())

    @property
    def num_records(self) -> int:
        # node metadata, not the summaries: must not fault spilled nodes in
        return sum(nd.n_records for nd in self.nodes) + self._buf_n

    # ------------------------------------------------------------ state
    def pack_state(self) -> dict:
        """Fixed-shape pytree of the full tree state (CheckpointManager-safe)."""
        cfg, cap, S = self.cfg, self._cap, self.cfg.max_summaries
        if len(self.nodes) > S:
            raise RuntimeError(f"{len(self.nodes)} summaries > {S} slots")
        pts = np.zeros((S, cap, cfg.dim), np.float32)
        wts = np.zeros((S, cap), np.float32)
        cand = np.zeros((S, cap), bool)
        valid = np.zeros((S, cap), bool)
        level = np.full((S,), -1, np.int32)
        min_seq = np.zeros((S,), np.int64)
        max_seq = np.zeros((S,), np.int64)
        count = np.zeros((S,), np.int64)
        node_epoch = np.zeros((S,), np.int64)
        for i, nd in enumerate(self.nodes):
            summ = self._node_summary(nd)   # checkpoints are self-contained
            s = summ.points.shape[0]
            pts[i, :s] = summ.points
            wts[i, :s] = summ.weights
            cand[i, :s] = summ.is_candidate
            valid[i, :s] = True
            level[i] = nd.level
            min_seq[i], max_seq[i], count[i] = nd.min_seq, nd.max_seq, nd.count
            node_epoch[i] = nd.epoch
        return {
            "points": pts, "weights": wts, "is_candidate": cand,
            "valid": valid, "level": level, "min_seq": min_seq,
            "max_seq": max_seq, "count": count, "node_epoch": node_epoch,
            "root_epoch": np.int64(self._epoch),
            "buffer": self._buf.copy(), "buffer_w": self._buf_w.copy(),
            "buffer_n": np.int64(self._buf_n),
            "flushed": np.int64(self._flushed),
            "total_ingested": np.int64(self.total_ingested),
            "key_data": np.asarray(jax.random.key_data(self.key)),
        }

    @classmethod
    def skeleton_state(cls, cfg: TreeConfig) -> dict:
        """Zero state with the shapes pack_state produces — the ``tree_like``
        argument CheckpointManager.restore needs."""
        return cls(cfg).pack_state()

    @classmethod
    def from_state(cls, cfg: TreeConfig, state: dict, *,
                   device: Optional[jax.Device] = None) -> "StreamTree":
        """The tree ``pack_state`` packed; it starts with no mirrors."""
        tree = cls(cfg, device=device)
        g = {k: np.asarray(v) for k, v in state.items()}
        tree.key = jax.random.wrap_key_data(
            jnp.asarray(g["key_data"], jnp.uint32))
        tree._buf = g["buffer"].astype(np.float32).copy()
        tree._buf_w = g["buffer_w"].astype(np.float32).copy()
        tree._buf_n = int(g["buffer_n"])
        tree._flushed = int(g["flushed"])
        tree.total_ingested = int(g["total_ingested"])
        tree._epoch = int(g["root_epoch"])
        for i in range(cfg.max_summaries):
            if int(g["level"][i]) < 0:
                continue
            v = g["valid"][i]
            summ = WeightedSummary(
                points=g["points"][i][v].astype(np.float32),
                weights=g["weights"][i][v].astype(np.float32),
                is_candidate=g["is_candidate"][i][v].astype(bool),
                n_rounds=0,
                total_weight=float(g["weights"][i][v].sum()))
            nd = tree._make_node(
                summ, level=int(g["level"][i]),
                min_seq=int(g["min_seq"][i]), max_seq=int(g["max_seq"][i]),
                count=int(g["count"][i]))
            nd.epoch = int(g["node_epoch"][i])
            tree.nodes.append(nd)
        tree._enforce_store()   # restored nodes re-obey the hot budget
        return tree
