"""Backend protocol + the shared per-op replay primitives.

A backend's :meth:`Backend.execute` replays one compiled
:class:`~repro.core.plan.ExecutionPlan` against a ``LocalExecutor``'s live
state.  The four primitives here are the *only* ways a backend touches that
state, and they must be applied **in plan order** for everything except the
op body itself:

* :func:`apply_ships`  — replay an op's precomputed transfer schedule;
* :func:`gather_args`  — resolve an op's payload arguments from the stores;
* :func:`resolve_call` — memoised executable-cache resolution for the body;
* :func:`commit`       — place written payloads, sample live peaks, run GC.

The frontend↔backend contract: during ``execute`` the executor's
``_round_counter`` still holds the segment's base round (the frontend
advances it by ``plan.n_rounds`` afterwards), and ``ops_executed`` /
``copies_elided`` / ``wavefronts`` accounting is the frontend's job.
Concurrent backends may reorder/overlap **op bodies** freely within one
wavefront level (the plan guarantees level-mates share no version
dependencies) but must keep ships and commits in plan order so the transfer
event stream stays byte-identical across backends.
"""

from __future__ import annotations

from ..stats import Span, TransferEvent, _nbytes


class RankFailure(RuntimeError):
    """A simulated rank failure, raised at a wavefront boundary.

    Carries everything the recovery planner (:mod:`repro.core.recovery`)
    needs: the lost ``rank``, the global ``wavefront`` ordinal the failure
    precedes (an index into ``ExecutionStats.wavefronts``), the
    plan-relative ``level`` ordinal (``None`` under the interpreter, which
    reports ``op_index`` instead), the failure ``kind`` (``"kill"`` wipes
    the rank's whole store, ``"ship"`` loses one in-flight replica listed
    in ``lost_keys``), and whether the rank is ``permanent``ly dead
    (triggering elastic rebind instead of transient recovery).
    """

    def __init__(self, rank: int, wavefront: int, *, level=None,
                 op_index=None, kind: str = "kill", permanent: bool = False,
                 lost_keys=None):
        super().__init__(
            f"rank {rank} {'lost a ship' if kind == 'ship' else 'failed'} "
            f"at wavefront {wavefront}"
            f"{' (permanent)' if permanent else ''}")
        self.rank = rank
        self.wavefront = wavefront
        self.level = level
        self.op_index = op_index
        self.kind = kind
        self.permanent = permanent
        self.lost_keys = lost_keys


class FaultInjector:
    """Deterministic seeded fault policies, consulted at wavefront boundaries.

    Every backend calls :meth:`check` once per wavefront level (the
    interpreter: once per op) *before* mutating any state for that level,
    so a raised :class:`RankFailure` always observes a consistent store.
    Policies are one-shot and fire at the **first** boundary whose global
    wavefront ordinal reaches their target (fused chains dispatch several
    levels atomically, so a mid-chain target fires at the chain's exit
    boundary).  The executor suspends the injector while a recovery
    sub-plan runs — recovery never re-faults itself.

    Construct via the policy classmethods (each returns a fresh injector,
    so a fuzzer replaying one scenario across backends builds one per run)
    or compose several policies with ``FaultInjector([...])``.
    """

    def __init__(self, policies=()):
        self.policies = [dict(p) for p in policies]
        self.fired: list[dict] = []
        self.delays = 0
        self.delay_s = 0.0
        self._suspended = 0

    # -- policy constructors -------------------------------------------------
    @classmethod
    def kill_rank(cls, rank: int, wavefront: int,
                  permanent: bool = False) -> "FaultInjector":
        """Kill rank ``rank`` at the first boundary >= ``wavefront``."""
        return cls([{"kind": "kill", "rank": rank, "wavefront": wavefront,
                     "permanent": permanent, "fired": False}])

    @classmethod
    def drop_ship(cls, wavefront: int, seed: int = 0) -> "FaultInjector":
        """Lose one replicated version from one holder rank (a transfer
        that never arrived) at the first boundary >= ``wavefront`` where a
        replica exists; ``seed`` picks the victim deterministically."""
        return cls([{"kind": "ship", "wavefront": wavefront, "seed": seed,
                     "fired": False}])

    @classmethod
    def delay_rank(cls, rank: int, wavefront: int,
                   seconds: float = 0.0) -> "FaultInjector":
        """A straggler, not a failure: counted (and optionally priced) but
        raising nothing — the plan's wavefront barrier absorbs it."""
        return cls([{"kind": "delay", "rank": rank, "wavefront": wavefront,
                     "seconds": seconds, "fired": False}])

    # -- executor-side protocol ----------------------------------------------
    @property
    def armed(self) -> bool:
        """True while an un-fired policy could still raise."""
        return (not self._suspended
                and any(not p["fired"] for p in self.policies))

    def suspend(self) -> None:
        self._suspended += 1

    def resume(self) -> None:
        self._suspended -= 1

    def _pick_replica(self, ex, seed: int):
        """Deterministic (version, holder) victim for a ship drop: a
        non-root replica of some multiply-held version, or None if nothing
        is replicated yet (the policy then waits for a later boundary)."""
        cands = sorted(
            (k, tuple(sorted(rs))) for k, rs in ex._where.items()
            if len(rs) >= 2)
        if not cands:
            return None
        vkey, ranks = cands[seed % len(cands)]
        return vkey, ranks[-1]

    def check(self, ex, wavefront: int, level=None, op_index=None) -> None:
        """Fire any due policy; raises :class:`RankFailure` for kill/ship."""
        if self._suspended:
            return
        for pol in self.policies:
            if pol["fired"] or wavefront < pol["wavefront"]:
                continue
            kind = pol["kind"]
            if kind == "delay":
                pol["fired"] = True
                self.delays += 1
                self.delay_s += pol.get("seconds", 0.0)
                continue
            if kind == "ship":
                victim = self._pick_replica(ex, pol.get("seed", 0))
                if victim is None:
                    continue
                vkey, dst = victim
                pol["fired"] = True
                self.fired.append(pol)
                raise RankFailure(dst, wavefront, level=level,
                                  op_index=op_index, kind="ship",
                                  lost_keys=(vkey,))
            pol["fired"] = True
            self.fired.append(pol)
            raise RankFailure(pol["rank"], wavefront, level=level,
                              op_index=op_index, kind="kill",
                              permanent=pol.get("permanent", False))


class Backend:
    """Dispatch strategy for a compiled plan (see package docstring)."""

    name = "base"

    def execute(self, ex, wf, plan) -> None:
        raise NotImplementedError

    def place(self, ex, rank: int, payload):
        """The payload to store for an initial array placed on ``rank``.

        Simulated backends store it as given; the mesh backend puts it on
        the rank's device while ranks map onto devices.
        """
        return payload

    def reset(self, ex) -> None:
        """Drop any backend-owned state tied to ``ex``'s current payloads.

        Called when the executor forgets its stores (a new ``Workflow``
        restarts the version-id streams, so every held key is stale).
        Simulated backends keep no payload state of their own — the
        process-pool backend overrides this to clear worker arenas.
        """

    def __repr__(self) -> str:
        return f"<{type(self).__name__} name={self.name!r}>"


class BatchBucket:
    """Residency bookkeeping for one fused dispatch's stacked result buffer.

    ``live`` holds the row indices whose store payload is still a lazy
    :class:`BatchSlice` of this buffer; ``rows`` maps each committed row to
    its version key.  Every path that removes a lazy row from the stores —
    GC, ship/fetch materialisation, spill — must :meth:`BatchSlice.release`
    it, so :func:`spill_dead_buckets` can tell a fully-consumed bucket (the
    chain-of-wavefronts case: drop the registry entry, nothing to do) from a
    partially-GC'd one whose survivors are pinning the whole buffer.
    """

    __slots__ = ("buffer", "n", "live", "rows")

    def __init__(self, buffer, n: int):
        self.buffer = buffer
        self.n = n
        self.live = set(range(n))
        self.rows: dict = {}            # row index -> version key


class BatchSlice:
    """Lazy view of row ``index`` of a fused bucket's stacked result buffer.

    Stored in the executor's stores like any payload; ``nbytes`` reports the
    member's (row's) size so transfer and live-set accounting stay identical
    to per-op execution.  ``materialize()`` pays the one slice dispatch when
    a boundary actually needs the row; ``release()`` tells the owning
    :class:`BatchBucket` the row no longer pins the stacked buffer (the
    caller has dropped or concretised its store entries).
    """

    __slots__ = ("buffer", "index", "_nb", "aval", "bucket")

    def __init__(self, buffer, index: int, nb: int, aval, bucket=None):
        self.buffer = buffer
        self.index = index
        self._nb = nb
        self.aval = aval        # element aval: the row's ShapedArray
        self.bucket = bucket

    @property
    def nbytes(self) -> int:
        return self._nb

    @property
    def shape(self):
        return self.aval.shape

    @property
    def dtype(self):
        return self.aval.dtype

    def materialize(self):
        return self.buffer[self.index]

    def release(self) -> None:
        if self.bucket is not None:
            self.bucket.live.discard(self.index)

    def __repr__(self) -> str:
        return f"BatchSlice({self.aval.str_short()}, row {self.index})"


def materialize(payload):
    """Resolve a possibly-lazy payload to a concrete array."""
    if type(payload) is BatchSlice:
        return payload.materialize()
    return payload


def materialize_rows(payloads: list) -> list:
    """``payloads`` with every lazy row concretised, in one ``bind.rows``
    span (no span where none is lazy)."""
    n = sum(type(a) is BatchSlice for a in payloads)
    if not n:
        return payloads
    with Span("bind.rows") as sp:
        out = [materialize(a) for a in payloads]
        sp.rows = n
    return out


def drop_versions(gc_keys, stores, where, key_bytes, live_b, live_c):
    """Apply an op's GC drop list; returns updated ``(live_bytes, live_c)``.

    The single source of the drop idiom every backend must apply: pop the
    version from every holder rank's store, release lazy
    :class:`BatchSlice` rows from their bucket (so
    :func:`spill_dead_buckets` sees the same row-liveness regardless of
    which backend executed the drop), and debit the live-footprint
    accounting.  Callers mirroring the executor's counters into locals
    pass and reassign them; others pass ``ex._live_bytes`` /
    ``ex._live_entries`` directly.
    """
    for dk in gc_keys:
        ranks = where.pop(dk)
        for r in ranks:
            dead = stores[r].pop(dk)
            if type(dead) is BatchSlice:
                dead.release()
        live_c -= len(ranks)
        live_b -= key_bytes.pop(dk, 0)
    return live_b, live_c


def spill_dead_buckets(ex) -> int:
    """Eagerly materialise surviving rows of partially-dead buckets.

    Once any of a bucket's rows have been GC'd (or fetched/shipped), a
    surviving lazy row would pin the *whole* stacked buffer — process
    residency exceeding ``stats.peak_live_bytes`` (which prices rows
    individually) by up to the batch width.  This pass concretises every
    surviving row of such a bucket and drops the buffer, making actual
    residency match the accounting; fully-live buckets are left lazy (the
    chain pass-through case) and fully-dead ones just leave the registry.
    Called by the fused backend at each level boundary and by the executor
    frontend at the end of each program flush — under stitching, seams
    *inside* a pending program no longer trigger it, so a bucket riding a
    seam-crossing chain stays lazy.  Returns the number of rows spilled.
    """
    buckets = ex._lazy_buckets
    if not any(0 < len(b.live) < b.n for b in buckets):
        for bucket in [b for b in buckets if len(b.live) != b.n]:
            buckets.discard(bucket)         # fully dead: just leaves
        return 0
    stores, where = ex._stores, ex._where
    with Span("bind.rows") as sp:
        for bucket in list(buckets):
            live = bucket.live
            if len(live) == bucket.n:   # untouched: stays one lazy buffer
                continue
            if live:
                buffer = bucket.buffer
                for idx in sorted(live):
                    vkey = bucket.rows.get(idx)
                    ranks = where.get(vkey) if vkey is not None else None
                    if not ranks:
                        continue
                    concrete = None
                    for r in ranks:
                        payload = stores[r].get(vkey)
                        if (type(payload) is BatchSlice
                                and payload.bucket is bucket):
                            if concrete is None:
                                concrete = buffer[idx]
                            stores[r][vkey] = concrete
                    if concrete is not None:
                        sp.rows += 1
                live.clear()
            buckets.discard(bucket)
    return sp.rows


def apply_ships(ex, p) -> None:
    """Replay ``p``'s precomputed ship schedule (plan order, main thread)."""
    stores, where = ex._stores, ex._where
    events = ex._stats.transfers
    base_round = ex._round_counter
    wavefront = ex._wavefront_base + p.level - 1
    for vkey, root, transfers in p.ships:
        payload = stores[root][vkey]
        nb = _nbytes(payload)
        ranks = where[vkey]
        for src, dst, kind, rel in transfers:
            stores[dst][vkey] = payload
            ranks.add(dst)
            ex._live_entries += 1
            events.append(
                TransferEvent(vkey, src, dst, nb, base_round + rel, kind,
                              wavefront))


def gather_args(ex, p, node) -> list:
    """Resolve ``p``'s call arguments (payloads from stores, constants inline)."""
    if ex.n_nodes == 1:
        store0 = ex._stores[0]
        return [store0[k] if k is not None else a[1]
                for k, a in zip(p.arg_keys, node.args)]
    # the executing rank's own copy when it holds one: with payloads placed
    # on devices (mesh backend), another rank's replica sits on another chip
    stores, where = ex._stores, ex._where
    local = stores[p.exec_ranks[0]]
    return [(local[k] if k in local else stores[next(iter(where[k]))][k])
            if k is not None else a[1]
            for k, a in zip(p.arg_keys, node.args)]


def resolve_call(ex, p, args):
    """Executable-cache resolution with the plan-op's type memo (main thread)."""
    types = tuple(map(type, args))
    if types == p.cached_types:
        return p.cached_call
    call = ex._exec_cache.lookup(p.fn, args)
    if call is p.fn:   # Python path: valid for any shapes
        # call before types: plans are shared process-wide, and a concurrent
        # replayer must never see matching types with the callable unset.
        p.cached_call = call
        p.cached_types = types
    else:              # jit path: shape-keyed, re-resolve per run
        p.cached_types = None
    return call


def commit(ex, p, node, result, nbytes=None) -> None:
    """Place ``p``'s written payloads, sample live peaks, apply GC.

    ``nbytes`` may carry a precomputed payload size for the simple-write
    case — fused buckets share one shape/dtype, so the (surprisingly
    costly) jax ``.nbytes`` property is paid once per bucket, not per op.
    """
    stores, where, key_bytes = ex._stores, ex._where, ex._key_bytes
    stats = ex._stats
    if p.simple_write and not isinstance(result, tuple):
        # dominant case: one payload, one executing rank
        wk = p.write_keys[0]
        nb = _nbytes(result) if nbytes is None else nbytes
        key_bytes[wk] = nb
        ex._live_bytes += nb
        rank = p.exec_ranks[0]
        where[wk] = {rank}
        stores[rank][wk] = result
        ex._live_entries += 1
    else:
        if not isinstance(result, tuple):
            result = (result,)
        assert len(result) == p.n_writes, (
            f"{node.name} returned {len(result)} payloads for "
            f"{p.n_writes} written args"
        )
        for wk, payload in zip(p.write_keys, result):
            nb = _nbytes(payload)
            key_bytes[wk] = nb
            ex._live_bytes += nb
            holders = set(p.exec_ranks)
            where[wk] = holders
            for rank in holders:
                stores[rank][wk] = payload
            ex._live_entries += len(holders)
    if ex._live_bytes > stats.peak_live_bytes:
        stats.peak_live_bytes = ex._live_bytes
    if ex._live_entries > stats.peak_live_payloads:
        stats.peak_live_payloads = ex._live_entries
    if p.gc_keys:
        ex._live_bytes, ex._live_entries = drop_versions(
            p.gc_keys, stores, where, key_bytes,
            ex._live_bytes, ex._live_entries)
