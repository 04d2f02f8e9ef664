"""Lowering Bind's implicit collectives onto the TPU mesh (hardware adaptation).

The paper's runtime turns the consumer queue of a version into a *binary tree*
of MPI point-to-point messages.  On a TPU mesh the point-to-point primitive is
``jax.lax.ppermute`` over a named axis, so the faithful lowering of the
paper's schedule is a log-depth sequence of ``ppermute`` rounds inside
``shard_map`` — these are :func:`tree_reduce`, :func:`tree_broadcast`,
:func:`tree_allreduce`.

Beyond-paper variants provided for the perf hillclimb (§Perf):

* :func:`ring_allreduce` — bandwidth-optimal reduce-scatter + all-gather as a
  single ``psum_scatter``/``all_gather`` pair (what XLA emits natively on a
  torus; 2·B·(n−1)/n bytes instead of the tree's 2·B·log₂n),
* :func:`hierarchical_allreduce` — pod-aware: reduce-scatter inside the pod,
  all-reduce the 1/n-sized shards across pods, all-gather inside the pod.
  Cross-pod traffic drops by the pod size — the schedule Bind's "partial
  collectives" machinery would discover given the two-level topology.

All functions are written to run *inside* ``shard_map`` (they use named axes)
and are validated in multi-device subprocess tests.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# Paper-faithful binary-tree collectives (log-depth ppermute schedules)
# ---------------------------------------------------------------------------

def tree_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Binary-tree reduction onto rank 0 of ``axis_name`` (paper's log reduction).

    Round ``s``: ranks ``i`` with ``i % 2s == s`` send their partial to
    ``i - s`` which accumulates.  After ⌈log₂ n⌉ rounds rank 0 holds the sum;
    other ranks hold garbage partials (callers follow with a broadcast or
    discard).  Mirrors Listing 1's ``for (s = 1; s < nt; s *= 2)`` loop.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    s = 1
    while s < n:
        pairs = [(i + s, i) for i in range(0, n - s, 2 * s)]
        y = lax.ppermute(x, axis_name, pairs)
        is_receiver = jnp.logical_and(idx % (2 * s) == 0, idx + s < n)
        x = jnp.where(is_receiver, x + y, x)
        s *= 2
    return x


def tree_broadcast(x: jax.Array, axis_name: str) -> jax.Array:
    """Binary-tree broadcast from rank 0 of ``axis_name`` (log₂ n rounds)."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    if n == 1:
        return x
    s = 1 << (int(math.ceil(math.log2(n))) - 1)
    while s >= 1:
        pairs = [(i, i + s) for i in range(0, n - s, 2 * s)]
        y = lax.ppermute(x, axis_name, pairs)
        is_receiver = idx % (2 * s) == s  # exactly the ranks first informed now
        x = jnp.where(is_receiver, y, x)
        s //= 2
    return x


def tree_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Paper-faithful all-reduce: binary-tree reduce to 0, then tree broadcast.

    Depth 2·log₂ n, bytes-on-wire per rank ≈ 2·B·log₂ n / n … B (root), versus
    the ring's uniform 2·B·(n−1)/n.  This is the *baseline* gradient-sync
    schedule (the paper's implicit collective); :func:`ring_allreduce` is the
    beyond-paper optimisation.
    """
    return tree_broadcast(tree_reduce(x, axis_name), axis_name)


# ---------------------------------------------------------------------------
# Beyond-paper schedules (hillclimb variants)
# ---------------------------------------------------------------------------

def ring_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Bandwidth-optimal all-reduce (XLA-native reduce-scatter + all-gather)."""
    return lax.psum(x, axis_name)


def reduce_scatter(x: jax.Array, axis_name: str, *, scatter_dimension: int = 0) -> jax.Array:
    return lax.psum_scatter(
        x, axis_name, scatter_dimension=scatter_dimension, tiled=True
    )


def all_gather(x: jax.Array, axis_name: str, *, axis: int = 0) -> jax.Array:
    return lax.all_gather(x, axis_name, axis=axis, tiled=True)


def hierarchical_allreduce(
    x: jax.Array, inner_axis: str, outer_axis: str, *, scatter_dimension: int = 0
) -> jax.Array:
    """Two-level (pod-aware) all-reduce.

    reduce-scatter over ``inner_axis`` (fast intra-pod ICI), all-reduce the
    1/inner-sized shard over ``outer_axis`` (scarce inter-pod links), then
    all-gather over ``inner_axis``.  Cross-pod bytes shrink by the pod size.
    """
    shard = lax.psum_scatter(
        x, inner_axis, scatter_dimension=scatter_dimension, tiled=True
    )
    shard = lax.psum(shard, outer_axis)
    return lax.all_gather(shard, inner_axis, axis=scatter_dimension, tiled=True)


GRAD_SYNC_SCHEDULES = ("tree", "ring", "hierarchical")


def allreduce_by_schedule(
    x: jax.Array,
    schedule: str,
    *,
    data_axes: tuple[str, ...],
    scatter_dimension: int | None = None,
) -> jax.Array:
    """Dispatch an all-reduce over (possibly several) data axes by schedule name.

    ``data_axes`` is ordered outermost-first, e.g. ``("pod", "data")``.  For
    the hierarchical schedule the scatter dimension is auto-picked as the
    first dim divisible by the inner axis size (falling back to a plain psum
    when no dim divides — e.g. tiny bias vectors, where the cross-pod saving
    is negligible anyway).
    """
    if schedule == "tree":
        for ax in data_axes:
            x = tree_allreduce(x, ax)
        return x
    if schedule == "ring":
        return lax.psum(x, data_axes)
    if schedule == "hierarchical":
        if len(data_axes) == 1:
            return lax.psum(x, data_axes[0])
        outer, inner = data_axes[0], data_axes[-1]
        scat = scatter_dimension
        if scat is None:
            inner_n = lax.axis_size(inner)
            scat = next(
                (d for d in range(x.ndim) if x.shape[d] % inner_n == 0), None
            )
        if scat is None:
            return lax.psum(x, data_axes)
        return hierarchical_allreduce(x, inner, outer, scatter_dimension=scat)
    raise ValueError(f"unknown schedule {schedule!r}; one of {GRAD_SYNC_SCHEDULES}")


# ---------------------------------------------------------------------------
# Rooted broadcasts (the mesh backend's ship lowering)
# ---------------------------------------------------------------------------
# A plan ship moves one version from its *root* holder to the destination
# ranks; the plan's TreeSchedule already fixes the accounting (the transfer
# stream replayed by every backend).  These are the corresponding *physical*
# schedules over a named mesh axis: every rank ends holding the root's
# shard.  ``tree`` is the log-depth lowering of the plan's broadcast tree;
# ``ring``/``hierarchical`` are the topology-model-selected alternatives
# (neighbour fabrics / switch trees), value-identical by construction —
# ppermute moves bytes, it never rounds.
#
# All three work from an arbitrary root by operating on *virtual* ranks
# ``v = (idx - root) mod n`` (the root plays virtual rank 0), so the pair
# lists are plain rotations of the root-0 schedules.

def tree_broadcast_from(x: jax.Array, axis_name: str, root: int = 0) -> jax.Array:
    """Binary-tree broadcast from ``root`` (log₂ n ppermute rounds)."""
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    v = (idx - root) % n
    s = 1 << (int(math.ceil(math.log2(n))) - 1)
    while s >= 1:
        pairs = [((i + root) % n, (i + s + root) % n)
                 for i in range(0, n - s, 2 * s)]
        y = lax.ppermute(x, axis_name, pairs)
        is_receiver = v % (2 * s) == s
        x = jnp.where(is_receiver, y, x)
        s //= 2
    return x


def ring_broadcast(x: jax.Array, axis_name: str, root: int = 0) -> jax.Array:
    """Neighbour-only broadcast: n−1 single-hop rounds around the ring.

    Linear depth but every round is a nearest-neighbour ppermute — the
    right schedule when the topology model says distant hops are expensive
    (a 1-D torus), and the baseline the tree must beat elsewhere.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    v = (idx - root) % n
    for s in range(1, n):
        y = lax.ppermute(x, axis_name,
                         [((root + s - 1) % n, (root + s) % n)])
        x = jnp.where(v == s, y, x)
    return x


def hierarchical_broadcast(x: jax.Array, axis_name: str, root: int = 0,
                           *, arity: int = 4) -> jax.Array:
    """Two-phase broadcast for switch-tree fabrics: leaders, then groups.

    Virtual ranks split into groups of ``arity``; phase 1 tree-broadcasts
    the root's shard across the group *leaders* (the cross-switch hops),
    phase 2 tree-broadcasts inside every group concurrently (the cheap
    intra-switch hops).  Cross-switch rounds drop to ⌈log₂⌈n/arity⌉⌉.
    """
    n = lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = lax.axis_index(axis_name)
    v = (idx - root) % n
    leaders = list(range(0, n, arity))
    m = len(leaders)
    if m > 1:                       # phase 1: binary tree over leaders
        s = 1 << (int(math.ceil(math.log2(m))) - 1)
        while s >= 1:
            pairs = [((leaders[i] + root) % n,
                      (leaders[i + s] + root) % n)
                     for i in range(0, m - s, 2 * s)]
            y = lax.ppermute(x, axis_name, pairs)
            is_receiver = jnp.logical_and(v % arity == 0,
                                          (v // arity) % (2 * s) == s)
            x = jnp.where(is_receiver, y, x)
            s //= 2
    g = min(arity, n)               # phase 2: trees inside each group
    s = 1 << max(0, int(math.ceil(math.log2(g))) - 1)
    while s >= 1:
        pairs = []
        for lead in leaders:
            size = min(arity, n - lead)
            for i in range(0, size - s, 2 * s):
                pairs.append(((lead + i + root) % n,
                              (lead + i + s + root) % n))
        if pairs:
            y = lax.ppermute(x, axis_name, pairs)
            x = jnp.where((v % arity) % (2 * s) == s, y, x)
        s //= 2
    return x


SHIP_SCHEDULES = ("tree", "ring", "hierarchical")


def broadcast_by_schedule(x: jax.Array, schedule: str, axis_name: str,
                          root: int = 0, *, arity: int = 4) -> jax.Array:
    """Dispatch a rooted broadcast by schedule name (value-identical)."""
    if schedule == "tree":
        return tree_broadcast_from(x, axis_name, root)
    if schedule == "ring":
        return ring_broadcast(x, axis_name, root)
    if schedule == "hierarchical":
        return hierarchical_broadcast(x, axis_name, root, arity=arity)
    raise ValueError(f"unknown schedule {schedule!r}; one of {SHIP_SCHEDULES}")


def schedule_for_topology(topology) -> str:
    """Ship schedule the :class:`~repro.launch.mesh.Topology` model prefers.

    Neighbour fabrics (``ring``) price distant hops by arc length — the
    single-hop pipeline wins; switch trees (``fat-tree``) price cross-switch
    hops double — the leader/group split wins; flat crossbars (and no
    topology at all) take the paper's log-depth tree.
    """
    kind = getattr(topology, "kind", None)
    if kind == "ring":
        return "ring"
    if kind == "fat-tree":
        return "hierarchical"
    return "tree"


# ---------------------------------------------------------------------------
# Whole-tree wrappers (operate on pytrees of gradients inside shard_map)
# ---------------------------------------------------------------------------

def sync_gradients(
    grads,
    schedule: str,
    data_axes: tuple[str, ...],
    *,
    mean: bool = True,
):
    """All-reduce every leaf of a gradient pytree with the chosen schedule."""
    n = 1
    for ax in data_axes:
        n *= lax.axis_size(ax)

    def _one(g):
        out = allreduce_by_schedule(g, schedule, data_axes=data_axes)
        return out / n if mean else out

    return jax.tree_util.tree_map(_one, grads)
