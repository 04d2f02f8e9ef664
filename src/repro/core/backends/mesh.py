"""Device-mesh dispatch: plan ships become ``shard_map`` collectives and
kernel-bodied chains become single ``pallas_call`` executables.

Every other backend *simulates* the distributed machine the plan was
compiled for — per-rank stores are dict entries, a ship is a dict insert.
This backend executes the same plan against a **real jax device mesh**
(CPU multi-device via ``XLA_FLAGS=--xla_force_host_platform_device_count``
in tests/CI; on TPU the identical build runs un-interpreted):

* **Ships** — plan ranks map 1:1 onto a named mesh axis ``"r"``.  Each
  op's precomputed ship schedule is lowered to the log-depth ``ppermute``
  broadcast rounds of :mod:`repro.core.lowering` (``tree`` / ``ring`` /
  ``hierarchical``, selected by the executor's
  :class:`~repro.launch.mesh.Topology` model), run inside one jitted
  ``shard_map`` over a row-sharded staging buffer whose root row holds the
  payload.  Destination ranks' stores then hold *their device's* broadcast
  row — bitwise-identical bits that physically travelled the collective.
* **Chains** — a :class:`~repro.core.plan.ChainSlice` whose op body
  carries a ``__bind_kernel__`` tag (the executor-callable entry points of
  ``repro.kernels.*.ops``) dispatches through
  :meth:`~repro.core.executable_cache.ExecutableCache.lookup_chain_pallas`:
  the whole chain compiles into ONE ``pallas_call`` whose kernel runs the
  levels as a ``fori_loop`` — instead of a python-level ``lax.scan`` of
  XLA calls.  Untagged bodies keep the generic scan path.

* **Placement** — while ship lowering is active, rank ``r``'s initial
  payloads are put on ``devices[r]`` and a shipped row lands on the
  destination's device, so rank ``r``'s op bodies run on chip ``r``.
  Fused buckets never mix ranks, and a chain dispatches only when it runs
  on one rank with no first-level ship (its operands then already sit on
  that rank's device).

The frontend contract is unchanged: commit/GC/transfer accounting is
replayed virtually in plan order (the procs-backend pattern), so values,
stats and the transfer-event stream stay **byte-identical to serial** and
the backend passes the cross-backend conformance fuzzer unchanged.
``ppermute`` moves bits without arithmetic and the pallas chain kernels
are bitwise-stable in interpret mode, so parity on the CPU is exact, not
approximate.

The mode follows the platform: on a TPU the chain kernels compile for the
chip (chain lowering is armed even with one device); elsewhere they run in
Pallas interpret mode and ``pallas="auto"`` arms them only on a device
mesh.  What is not lowered, and never an error:

* fewer than 2 devices, or more plan ranks than devices → ships replay
  simulated (inherited :class:`~.fused.FusedBatchBackend` behaviour);
* a non-jax / empty payload → that ship replays simulated;
* an untagged chain body, width > 1, or a non-width-1 layout → that
  chain takes the generic ``jit(lax.scan)`` path.

What is lowered must succeed: a collective or a tagged chain's Pallas
kernel that fails to build or run raises — the flush fails under the
executor's failure contract — instead of quietly replaying on another
path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..lowering import broadcast_by_schedule, schedule_for_topology
from ..stats import TransferEvent, _nbytes
from .base import BatchSlice
from .fused import CONST, SINGLE, XS, XS_CONST, FusedBatchBackend

# layouts a width-1 pallas chain executable understands (FLAT/STACKED are
# width>1 shapes; they keep the generic scan path)
_PALLAS_LAYOUTS = frozenset((SINGLE, CONST, XS, XS_CONST))


class MeshBackend(FusedBatchBackend):
    """Execute a compiled plan on a real jax device mesh (see module doc).

    ``schedule`` pins the ship-lowering collective (``"tree"`` | ``"ring"``
    | ``"hierarchical"``); default derives it from the executor's topology
    model via :func:`~repro.core.lowering.schedule_for_topology`.

    ``pallas`` gates chain lowering: ``"auto"`` (default) enables it on a
    TPU, and elsewhere exactly when ship lowering can be active (≥ 2
    devices — single-device CPU hosts fall back to ``fused`` wholesale);
    ``True`` forces it on any host (interpret mode runs on one CPU device;
    the test suite uses this to counter-assert dispatch without a
    multi-device subprocess), and ``False`` disables it.  Whether the
    kernels are interpreted is not an option: it follows the platform
    (:attr:`interpret`).
    """

    name = "mesh"

    def __init__(self, min_batch: int = 2, min_chain_levels: int = 2, *,
                 schedule: str | None = None, pallas="auto"):
        super().__init__(min_batch, min_chain_levels)
        self.schedule = schedule
        self.pallas = pallas
        self._devices = tuple(jax.devices())
        # Pallas kernels compile for real only on a TPU; on the CPU they
        # run through the interpreter
        self.interpret = self._devices[0].platform != "tpu"
        self._active = False            # ship lowering armed for this plan?
        self._schedule_eff = "tree"     # resolved per execute()
        self._arity = 4
        self._meshes: dict[int, Mesh] = {}
        self._bcast_cache: dict[tuple, object] = {}
        # observability: counter-asserted by tests/benchmarks
        self.ships_lowered = 0          # ship schedules run as collectives
        self.ships_simulated = 0        # ship schedules replayed simulated
        self.pallas_chains_dispatched = 0
        self.ops_pallas = 0

    # -- per-plan arming ------------------------------------------------------
    def _pallas_enabled(self) -> bool:
        if self.pallas == "auto":
            return not self.interpret or len(self._devices) >= 2
        return bool(self.pallas)

    def _lowers_ships(self, n_nodes: int) -> bool:
        """Ranks map onto devices (ships lower, payloads are placed)."""
        return len(self._devices) >= 2 and 2 <= n_nodes <= len(self._devices)

    def execute(self, ex, wf, plan) -> None:
        self._active = self._lowers_ships(ex.n_nodes)
        if self._active:
            topo = getattr(ex, "topology", None)
            self._schedule_eff = (self.schedule
                                  or schedule_for_topology(topo))
            self._arity = max(2, int(getattr(topo, "arity", 4) or 4))
        super().execute(ex, wf, plan)

    def place(self, ex, rank: int, payload):
        if self._lowers_ships(ex.n_nodes) and isinstance(payload, jax.Array):
            return jax.device_put(payload, self._devices[rank])
        return payload

    def misplaced(self, ex) -> list:
        """``(rank, version key)`` of every stored device payload that does
        not live on its rank's device while ranks map onto devices (empty
        otherwise) — the placement invariant, for tests and smoke runs."""
        if not self._lowers_ships(ex.n_nodes):
            return []
        bad = []
        for rank, store in ex._stores.items():
            want = {self._devices[rank]}
            for vkey, payload in store.items():
                arr = (payload.buffer if type(payload) is BatchSlice
                       else payload)
                if isinstance(arr, jax.Array) and arr.devices() != want:
                    bad.append((rank, vkey))
        return bad

    def _delegate_wholesale(self, ex, wf, plan) -> bool:
        # while lowering is armed, multi-rank plans stay on the level loop
        # so their ships actually reach the collective path (serial replays
        # ships inline, simulated)
        if self._active and ex.n_nodes >= 2:
            return False
        return super()._delegate_wholesale(ex, wf, plan)

    # -- ship lowering --------------------------------------------------------
    def _mesh_for(self, n: int) -> Mesh:
        mesh = self._meshes.get(n)
        if mesh is None:
            mesh = Mesh(np.array(self._devices[:n]), ("r",))
            self._meshes[n] = mesh
        return mesh

    def _bcast_call(self, n: int, root: int, shape, dtype):
        """Jitted ``shard_map`` broadcast over the ``n``-rank mesh axis,
        cached per ``(n, root, schedule, shape, dtype)``."""
        key = (n, root, self._schedule_eff, shape, str(dtype))
        call = self._bcast_cache.get(key)
        if call is None:
            mesh = self._mesh_for(n)
            sched, arity = self._schedule_eff, self._arity
            spec = P("r", *(None,) * len(shape))

            def body(x):
                return broadcast_by_schedule(x, sched, "r", root=root,
                                             arity=arity)

            smapped = jax.shard_map(body, mesh=mesh, in_specs=spec,
                                    out_specs=spec, check_vma=False)
            call = (jax.jit(smapped), mesh, spec)
            self._bcast_cache[key] = call
        return call

    def _broadcast_rows(self, payload, root: int, n: int):
        """Run one rooted broadcast on the device mesh; returns the ``n``
        received ``(1, *shape)`` shards, shard ``r`` on ``devices[r]``."""
        call, mesh, spec = self._bcast_call(
            n, root, payload.shape, payload.dtype)
        # root row carries the payload, every other row is zeros — the
        # collective must really move the bits (a broken schedule shows up
        # as zero rows, not silently-correct replicas)
        buf = jnp.zeros((n,) + payload.shape, payload.dtype)
        buf = buf.at[root].set(payload)
        buf = jax.device_put(buf, NamedSharding(mesh, spec))
        out = call(buf)
        shards = {s.device: s.data for s in out.addressable_shards}
        return [shards[d] for d in self._devices[:n]]

    def _apply_ships(self, ex, p) -> None:
        if not self._active:
            super()._apply_ships(ex, p)
            return
        self._materialize_shipped(ex, p)
        n = ex.n_nodes
        stores, where = ex._stores, ex._where
        events = ex._stats.transfers
        base_round = ex._round_counter
        wavefront = ex._wavefront_base + p.level - 1
        for vkey, root, transfers in p.ships:
            payload = stores[root][vkey]
            rows = None
            if isinstance(payload, jax.Array) and payload.size:
                rows = self._broadcast_rows(payload, root, n)
            if rows is None:
                self.ships_simulated += 1
            else:
                self.ships_lowered += 1
            # virtual replay: the plan's precomputed transfer schedule is
            # emitted verbatim (byte-identical stream); only the payload a
            # destination rank holds differs — its own broadcast row
            nb = _nbytes(payload)
            ranks = where[vkey]
            for src, dst, kind, rel in transfers:
                stores[dst][vkey] = (payload if rows is None
                                     else rows[dst][0])
                ranks.add(dst)
                ex._live_entries += 1
                events.append(
                    TransferEvent(vkey, src, dst, nb, base_round + rel,
                                  kind, wavefront))

    # -- rank-local fusion while payloads are placed ----------------------------
    def _run_bucket(self, ex, staged, members, results, result_nbytes) -> None:
        if not self._active:
            super()._run_bucket(ex, staged, members, results, result_nbytes)
            return
        # a stacked dispatch runs on one device: bucket per executing rank
        by_rank: dict[int, list] = {}
        for m in members:
            by_rank.setdefault(staged[m][0].exec_ranks[0], []).append(m)
        for group in by_rank.values():
            if len(group) >= self.min_batch:
                super()._run_bucket(ex, staged, group, results,
                                    result_nbytes)

    def _run_chain(self, ex, ops, plan, chain) -> bool:
        if self._active:
            schedule = plan.schedule
            ranks = {schedule[i].exec_ranks for lvl in chain.members
                     for i in lvl}
            if (len(ranks) != 1 or len(next(iter(ranks))) != 1
                    or any(schedule[i].ships for i in chain.members[0])):
                return False    # operands span devices: per-level path
        return super()._run_chain(ex, ops, plan, chain)

    # -- chain lowering -------------------------------------------------------
    def _dispatch_chain(self, ex, chain, layout, width, n_levels, carry_pos,
                        call_args, sig_args):
        if (width == 1 and chain.lowerable is not None
                and self._pallas_enabled()
                and set(layout) <= _PALLAS_LAYOUTS):
            # a tagged body asserts it lowers: a failure here raises
            call = ex._exec_cache.lookup_chain_pallas(
                chain.fn, layout, n_levels, carry_pos, sig_args,
                interpret=self.interpret)
            out = call(*call_args)
            self.pallas_chains_dispatched += 1
            self.ops_pallas += n_levels
            return out
        return super()._dispatch_chain(ex, chain, layout, width, n_levels,
                                       carry_pos, call_args, sig_args)
