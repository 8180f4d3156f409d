"""Shared mesh / shard_map plumbing for the coordinator model.

Every "one round of communication" program in this repo has the same shape:
a per-site function runs under ``shard_map`` over a 1-D ``sites`` axis, does
local work, exchanges fixed-shape payloads with a single ``all_gather``, and
finishes with a replicated coordinator step whose result is identical on
every site.  The one-shot path (``repro.core.distributed``) and the sharded
streaming path (``repro.stream.sharded``) both follow it; this module holds
the plumbing they would otherwise duplicate:

* ``sites_mesh``         — the canonical 1-D mesh over ``sites``;
* ``put_sites``          — lay per-site host payloads out over the mesh,
                           each site's straight onto its own device;
* ``gather_sites``       — all_gather a pytree over the axis and collapse
                           the site dim, i.e. "send every site's summary to
                           the coordinator" as one collective;
* ``replicated_coordinator`` — wraps the per-site fn so callers stop hand
                           rolling the ``[None]`` / take-``[0]`` dance for
                           replicated outputs;
* ``payload_bytes`` / ``gathered_bytes`` — communication accounting: the
  bytes one site contributes to an all_gather, and the total a refresh puts
  on the wire.  The paper measures communication in summary records; these
  give the byte-level view the benchmarks report alongside it.
"""
from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def sites_mesh(n_sites: int | None = None, *, axis: str = "sites") -> Mesh:
    """1-D mesh over ``axis``: one site per device, on the first ``n_sites``
    devices (default: all devices).

    The axis is ``Auto``: the replicated coordinator result is read back
    with a plain ``a[0]`` outside the ``shard_map``, which an ``Explicit``
    axis (``jax.make_mesh``'s default) refuses to resolve."""
    n = n_sites or len(jax.devices())
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:n])


def put_sites(per_site, mesh: Mesh, *, axis: str = "sites"):
    """Site-stacked device arrays of a list of per-site pytrees.

    ``per_site[i]`` (host arrays, the same shapes on every site) goes
    straight to device i of ``mesh`` along ``axis``, and each leaf becomes
    one ``(s, ...)`` array laid out ``P(axis)``: what a ``shard_map`` over
    ``axis`` takes without moving a byte, and what a deployment holds,
    where each site's payload already lives on its own chip.  Nothing is
    stacked on the host or staged on one device."""
    devices = list(mesh.devices.flat)
    if len(per_site) != len(devices):
        raise ValueError(f"{len(per_site)} site payloads for a mesh of "
                         f"{len(devices)} devices")
    sharding = NamedSharding(mesh, P(axis))

    def put(*leaves):
        blocks = [jax.device_put(np.expand_dims(a, 0), d)
                  for a, d in zip(leaves, devices)]
        return jax.make_array_from_single_device_arrays(
            (len(blocks),) + np.shape(leaves[0]), sharding, blocks)

    return jax.tree_util.tree_map(put, *per_site)


def gather_sites(tree, axis: str = "sites"):
    """Inside shard_map: all_gather every leaf over ``axis`` and collapse the
    gathered site dim, so a per-site ``(cap, ...)`` leaf becomes the
    coordinator's ``(s * cap, ...)`` union.  THE one round of communication:
    on hardware this lowers to one ICI collective per leaf."""

    def g(a):
        ga = jax.lax.all_gather(a, axis)          # (s, cap, ...)
        return ga.reshape((-1,) + ga.shape[2:])   # (s * cap, ...)

    return jax.tree_util.tree_map(g, tree)


def replicated_coordinator(per_site, mesh: Mesh, *, axis: str = "sites",
                           n_sharded: int = 1):
    """shard_map ``per_site`` over ``axis`` and unstack its replicated result.

    The first ``n_sharded`` arguments are sharded on their leading dim (each
    site sees its block with the leading site dim kept, length 1); remaining
    arguments are replicated.  ``per_site`` must return a pytree of arrays
    that is *identical on every site* (the coordinator result after a
    ``gather_sites``); the wrapper stacks them over sites and returns site
    0's copy, on site 0's device, so callers get the coordinator view
    directly.  It must not stay laid out across the mesh: the serving
    path feeds it to single-device programs, and a Pallas kernel cannot
    be partitioned over a mesh.

    The returned callable is jit-wrapped around one stable closure per
    argument count, so repeated invocations (e.g. every streaming refresh)
    reuse the compiled program instead of re-tracing — hold on to it.
    """

    def wrapped(*args):
        out = per_site(*args)
        return jax.tree_util.tree_map(lambda a: a[None], out)

    programs: dict[int, object] = {}   # arg count -> jitted shard_map program

    def call(*args):
        if len(args) < n_sharded:
            raise ValueError(f"{len(args)} args but n_sharded={n_sharded}")
        fn = programs.get(len(args))
        if fn is None:
            in_specs = tuple(P(axis) if i < n_sharded else P()
                             for i in range(len(args)))
            # check_vma=False: the per-site program calls Pallas kernels,
            # whose outputs carry no varying-axes annotation, and the
            # result is replicated by construction (it follows the gather)
            fn = jax.jit(jax.shard_map(wrapped, mesh=mesh,
                                       in_specs=in_specs, out_specs=P(axis),
                                       check_vma=False))
            programs[len(args)] = fn
        return jax.tree_util.tree_map(_site0, fn(*args))

    return call


def _site0(a):
    """Site 0's block of a site-stacked array, as a one-device array."""
    shard = next(s for s in a.addressable_shards
                 if s.index[0].start in (None, 0))
    return shard.data[0]


def payload_bytes(tree) -> int:
    """Bytes one site contributes to an all_gather of ``tree`` (its padded
    per-site payload — what actually crosses the interconnect, as opposed to
    the paper's valid-record count)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        dt = np.dtype(leaf.dtype)
        total += int(math.prod(leaf.shape)) * dt.itemsize
    return total


def gathered_bytes(tree, n_sites: int) -> int:
    """Total bytes one all_gather of per-site ``tree`` moves: every one of
    the ``n_sites`` participants contributes its payload once."""
    return payload_bytes(tree) * n_sites
