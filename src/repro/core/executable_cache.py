"""Jitted executable cache — compile an op signature once, replay forever.

The dominant pattern in tiled linalg and MapReduce workflows is thousands of
ops sharing a handful of *signatures* ``(fn, abstract shapes, dtypes)``: every
leaf GEMM of a Strassen recursion, every per-tile ``iadd``, every bucket sort.
The interpreter paid Python dispatch (and, for JAX payloads, re-tracing) per
call; this cache resolves each signature to an *executable* exactly once:

* **JAX payloads** → one ``jax.jit``-compiled executable per signature,
  replayed as a cached XLA computation (the KaMPIng-style "plan once, replay
  cheap" hot path);
* **NumPy / other payloads** → the raw Python callable (a NumPy 8×8 multiply
  beats XLA dispatch latency, so jitting would be a pessimisation) — the
  cache still memoises the jit-vs-python decision per signature.

Semantics are preserved exactly: NumPy payloads never silently become JAX
arrays (which would flip float64 → float32 under default jax config), and a
signature whose first jitted call raises falls back to the Python callable
permanently.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable

import jax
import numpy as np


def _named(call: Callable, kind: str, fn: Callable) -> Callable:
    """``call`` renamed ``<kind>_<fn's name>``: its executable is then
    ``jit_<kind>_<name>`` in a profiler trace."""
    call.__name__ = call.__qualname__ = (
        f"{kind}_{getattr(fn, '__name__', 'fn')}")
    return call


def _abstract(arg: Any):
    """Abstract signature component of one payload: shape/dtype or type.

    ``np.dtype`` objects are hashable and cheap to compare — never
    stringified (``str(dtype)`` costs ~µs and used to dominate replay).
    """
    t = type(arg)
    if t is np.ndarray:
        return (arg.shape, arg.dtype, False)
    shape = getattr(arg, "shape", None)
    dtype = getattr(arg, "dtype", None)
    if shape is not None and dtype is not None:
        return (shape, dtype, isinstance(arg, jax.Array))
    return t


def _read_rows(n_buffers: int, n_payloads: int, flat: tuple, pos: int):
    """The stacked column of a ``("rows", n_buffers, n_payloads)`` position
    whose operands start at ``flat[pos]`` (see
    :meth:`ExecutableCache.lookup_vmapped`), and the position after them.

    One loop over the members copies each row out of its source, so the
    trace (and the executable's set-up in every process) grows with the
    sources, not with the members, and no source is copied whole.
    """
    end = pos + n_buffers + n_payloads
    sources = list(flat[pos:pos + n_buffers])
    if n_payloads:
        sources.append(jax.numpy.stack(flat[pos + n_buffers:end]))
    rows = [functools.partial(jax.lax.dynamic_index_in_dim, source,
                              keepdims=False) for source in sources]
    if len(rows) == 1:
        def member(pick):
            return rows[0](pick[1])
    else:
        def member(pick):
            return jax.lax.switch(pick[0], rows, pick[1])
    return jax.lax.map(member, flat[end]), end + 1


MAX_ENTRIES = 1024


class ExecutableCache:
    """Signature-keyed executable store with hit/miss/compile counters.

    Bounded: past ``MAX_ENTRIES`` signatures the table is reset (entries pin
    op functions and XLA executables; a reset only costs recompiles, and hot
    signatures repopulate immediately).
    """

    __slots__ = ("_entries", "hits", "misses", "compiles", "fallbacks")

    def __init__(self):
        self._entries: dict[tuple, Callable] = {}
        self.hits = 0
        self.misses = 0
        self.compiles = 0      # signatures that produced a live XLA executable
        self.fallbacks = 0     # jit candidates that raised and fell back

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        self._entries.clear()
        self.hits = self.misses = self.compiles = self.fallbacks = 0

    def signature(self, fn: Callable, args) -> tuple:
        return (fn,) + tuple(_abstract(a) for a in args)

    def lookup(self, fn: Callable, args) -> Callable:
        """Resolve ``fn`` for these payloads; O(1) dict hit on replay."""
        key = self.signature(fn, args)
        entry = self._entries.get(key)
        if entry is not None:
            self.hits += 1
            return entry
        self.misses += 1
        if len(self._entries) >= MAX_ENTRIES:
            self._entries.clear()
        entry = self._build(key, fn, args)
        self._entries[key] = entry
        return entry

    def _resolve(self, key: tuple, build: Callable) -> Callable:
        """Memoise-or-build scaffolding shared by the batched/chain paths.

        On a miss, ``build()`` produces the jitted executable and the entry
        installed is a *first-call validator*: if the first replay's trace
        raises, the entry is evicted (a broken executable is never replayed
        — the caller falls back and should stop requesting this shape);
        on success it self-replaces with the raw jitted callable.
        """
        entry = self._entries.get(key)
        if entry is not None:
            self.hits += 1
            return entry
        self.misses += 1
        if len(self._entries) >= MAX_ENTRIES:
            self._entries.clear()
        jitted = build()
        cache = self

        def first_call(*call_args):
            try:
                out = jitted(*call_args)
            except Exception:
                cache._entries.pop(key, None)
                raise
            cache.compiles += 1
            cache._entries[key] = jitted
            return out

        self._entries[key] = first_call
        return first_call

    def lookup_vmapped(self, fn: Callable, layout: tuple, n_batch: int,
                       sig_args) -> Callable:
        """Resolve the *batched* executable for ``n_batch`` fused ops.

        ``layout`` describes each argument position of the flat call list:
        ``"flat"`` — ``n_batch`` consecutive member payloads, stacked inside
        the jitted body; ``"stacked"`` — one pre-stacked buffer passed
        through whole (the fused backend's batched-residency fast path);
        ``("rows", n_buffers, n_payloads)`` — member rows read inside the
        jitted body: ``n_buffers`` stacked source buffers, ``n_payloads``
        concrete member payloads, then an int32 ``(n_batch, 2)`` array
        whose row ``j`` is ``(source, row)``: member ``j`` is that row of
        that buffer, or, where ``source`` is ``n_buffers``, that payload;
        ``"const"`` — one shared constant, broadcast by vmap.  The entry
        runs ``vmap(fn)`` over the batch and returns the **stacked** result
        buffer — callers keep per-member rows as lazy views, so a fused
        level costs one dispatch and one result buffer, not N.

        ``sig_args`` holds one representative per position (first member
        payload / buffer / constant), and every source buffer of a
        ``"rows"`` position; constants and picks stay call arguments, so
        buckets differing only in constant *values* or in the rows they
        pick share the executable.

        Tracing failures are the caller's problem (it falls back to per-op
        dispatch and should stop requesting batches for that ``fn``); the
        entry is evicted so a broken executable is never replayed.
        """
        key = (fn, layout, n_batch) + tuple(_abstract(a) for a in sig_args)
        return self._resolve(key, lambda: bucket_call(fn, layout, n_batch))

    def lookup_chain(self, fn: Callable, layout: tuple, n_batch: int,
                     n_levels: int, carry_pos: int, sig_args) -> Callable:
        """Resolve the *chain* executable: ``n_levels`` consecutive
        applications of ``fn`` fused into one ``jit(lax.scan)`` dispatch.

        ``carry_pos`` names the payload position threaded through the scan
        as the loop state; its layout is ``"single"`` (one array,
        ``n_batch == 1``), ``"flat"`` (``n_batch`` member payloads stacked
        inside the jitted body) or ``"stacked"`` (one pre-stacked buffer
        passed through whole).  Other positions:

        * ``"single"`` / ``"flat"`` / ``"stacked"`` at a non-carry position
          — a chain-invariant *exterior* payload (a binary-op chain's other
          operand when every level reads the same version): closed over by
          the scan body, batched by ``vmap`` when ``n_batch > 1``;
        * ``"xs"`` — a per-level *varying* exterior payload, pre-stacked to
          ``(n_levels, [n_batch,] ...)`` and scanned as ``xs`` (each step
          consumes its own level's slice);
        * ``"xs_const"`` — per-level varying constants hoisted into one
          stacked ``(n_levels,)`` array and scanned as ``xs`` (broadcast
          across the batch);
        * ``"const"`` — one scan-invariant constant, kept a call argument
          so chains differing only in constant *values* share the
          executable (hoisted ``"xs_const"`` arrays share it too — the key
          sees their aval, not their values).

        The entry returns the **final** level's stacked result — a chain of
        ``n_levels × n_batch`` ops costs exactly one dispatch, and interior
        levels never materialise.

        ``lax.scan`` requires the carry aval to be loop-invariant, so a
        chain whose ``fn`` changes shape/dtype (or is not traceable) raises
        at trace time — the caller falls back to per-level dispatch and the
        entry is evicted so a broken executable is never replayed.
        """
        key = ((fn, "chain", layout, n_batch, n_levels, carry_pos)
               + tuple(_abstract(a) for a in sig_args))
        xs_positions = tuple(i for i, lay in enumerate(layout)
                             if lay in ("xs", "xs_const"))
        in_axes = tuple(None if lay in ("const", "xs_const") else 0
                        for lay in layout)
        body = fn if n_batch == 1 else jax.vmap(fn, in_axes=in_axes)

        def build():
            def chain_call(*flat):
                args = []
                pos = 0
                for lay in layout:
                    if lay == "flat":
                        args.append(jax.numpy.stack(flat[pos:pos + n_batch]))
                        pos += n_batch
                    else:       # "single"/"stacked"/"const"/"xs"/"xs_const"
                        args.append(flat[pos])
                        pos += 1

                def step(carry, xs_slice):
                    call_args = list(args)
                    call_args[carry_pos] = carry
                    if xs_positions:
                        for p, x in zip(xs_positions, xs_slice):
                            call_args[p] = x
                    out = body(*call_args)
                    if isinstance(out, tuple):
                        out = out[0]    # chain ops write exactly one payload
                    return out, None

                xs = (tuple(args[p] for p in xs_positions)
                      if xs_positions else None)
                final, _ = jax.lax.scan(step, args[carry_pos], xs,
                                        length=n_levels)
                return final

            return jax.jit(_named(chain_call, "bind_chain", fn))

        return self._resolve(key, build)

    def lookup_chain_pallas(self, fn: Callable, layout: tuple, n_levels: int,
                            carry_pos: int, sig_args, *,
                            interpret: bool) -> Callable:
        """Resolve a *Pallas* chain executable: the whole ``n_levels`` run of
        a width-1 kernel-bodied chain compiled into ONE ``pl.pallas_call``
        (built by :func:`chain_pallas_call`, which documents the tiling).

        Where :meth:`lookup_chain` scans a python-level ``fn`` with
        ``lax.scan`` (one XLA loop around per-level ops), this lowers the
        chain *into* a Pallas kernel and writes only the final carry.
        ``interpret=True`` executes the kernel on CPU; on TPU the same
        build compiles for real.  Only op bodies annotated
        ``__bind_kernel__`` (the executor-callable entry points of
        ``repro.kernels.*.ops``) may be resolved here — the tag asserts the
        body is a pure shape-preserving array function a Pallas block can
        evaluate, so a trace or lowering failure is an error: it raises
        (the entry is evicted, see :meth:`_resolve`).

        Layout vocabulary is the width-1 subset of :meth:`lookup_chain`:
        ``"single"`` (carry or chain-invariant exterior), ``"xs"`` /
        ``"xs_const"`` (per-level varying, stacked to ``(n_levels, ...)``),
        and ``"const"``.  Constants are **static** here (they bake into the
        kernel; the cache key carries their values) so the kernel body sees
        exactly the python scalars serial replay passes — Pallas operands
        would round-trip them through arrays and could flip a weak dtype.
        """
        key = ((fn, "chain_pallas", layout, n_levels, carry_pos, interpret)
               + tuple(("const", a) if lay == "const" else _abstract(a)
                       for lay, a in zip(layout, sig_args)))
        return self._resolve(key, lambda: chain_pallas_call(
            fn, layout, n_levels, carry_pos, interpret=interpret))

    # -- entry construction ---------------------------------------------------
    def _build(self, key: tuple, fn: Callable, args) -> Callable:
        array_args = [a for a in args
                      if getattr(a, "shape", None) is not None
                      and getattr(a, "dtype", None) is not None]
        use_jit = (bool(array_args)
                   and all(isinstance(a, jax.Array) for a in array_args)
                   and not getattr(fn, "__bind_nojit__", False))
        if not use_jit:
            return fn
        jitted = jax.jit(fn)
        cache = self

        def first_call(*call_args):
            # Compile lazily at the first replay; if the op body is not
            # jit-traceable (data-dependent Python control flow, host-only
            # types), pin the signature to the Python path instead of
            # failing the workflow.  Only tracing-class errors fall back —
            # runtime failures (OOM, real bugs) must propagate, and the
            # fallback re-executes the body, so it is reserved for bodies
            # whose trace never completed.
            try:
                out = jitted(*call_args)
            except (jax.errors.JAXTypeError, TypeError):
                cache.fallbacks += 1
                cache._entries[key] = fn
                return fn(*call_args)
            cache.compiles += 1
            cache._entries[key] = jitted
            return out

        return first_call


def bucket_call(fn: Callable, layout: tuple, n_batch: int) -> Callable:
    """Build the jitted ``vmap(fn)`` over ``n_batch`` fused ops whose flat
    arguments follow ``layout`` (see :meth:`ExecutableCache.lookup_vmapped`);
    it returns the stacked result."""
    in_axes = tuple(None if lay == "const" else 0 for lay in layout)

    def stacked_call(*flat):
        args = []
        pos = 0
        for lay in layout:
            if lay == "flat":
                args.append(jax.numpy.stack(flat[pos:pos + n_batch]))
                pos += n_batch
            elif type(lay) is tuple:    # ("rows", n_buffers, n_payloads)
                column, pos = _read_rows(lay[1], lay[2], flat, pos)
                args.append(column)
            else:                       # "stacked" buffer or "const"
                args.append(flat[pos])
                pos += 1
        out = jax.vmap(fn, in_axes=in_axes)(*args)
        if isinstance(out, tuple):
            out = out[0]                # fused ops write exactly one payload
        return out

    return jax.jit(_named(stacked_call, "bind_bucket", fn))


# VMEM the chain kernel's pipelined blocks may take (double buffers included)
# when it picks a row-block height, and the scoped VMEM limit it asks the
# compiler for; the gap is room for the op body's temporaries.  A TPU v5e
# TensorCore has 128 MiB of VMEM, the compiler's default scope is 16 MiB.
CHAIN_BLOCK_BUDGET = 24 << 20
CHAIN_VMEM_LIMIT = 64 << 20
_MAX_BLOCK_ROWS = 512


def _row_positions(fn: Callable, layout: tuple) -> frozenset:
    """Argument positions a chain kernel may cut into row blocks.

    The ``__bind_kernel__`` tag says how the body treats its rows:
    ``"ewise"`` bodies are elementwise in every operand; ``"dot"`` bodies
    (``c + a @ b``, ``o + softmax(q kᵀ) v``) are row-separable in their
    first two arguments — the carry and its row-aligned left operand —
    while the later ones are contraction partners every row block reads
    whole.  Hoisted per-level scalars live in SMEM and are never blocked.
    """
    kind = getattr(fn, "__bind_kernel__", None)
    tensors = [p for p, lay in enumerate(layout)
               if lay not in ("const", "xs_const")]
    if kind == "ewise":
        return frozenset(tensors)
    if kind == "dot":
        return frozenset(p for p in (0, 1) if p in tensors)
    return frozenset()


def _block_rows(rows: int, itemsize: int, row_bytes: int,
                whole_bytes: int) -> int:
    """Row-block height: the largest legal divisor of ``rows`` (a multiple
    of the dtype's sublane tile, at most ``_MAX_BLOCK_ROWS``) whose double
    buffers fit :data:`CHAIN_BLOCK_BUDGET`; the smallest legal one when
    none fits (the compiler then says what is over), ``rows`` when no
    divisor is legal."""
    step = 8 * max(1, 4 // itemsize)
    cands = [b for b in range(step, min(rows, _MAX_BLOCK_ROWS) + 1, step)
             if rows % b == 0]
    if not cands:
        return rows
    fitting = [b for b in cands
               if 2 * (b * row_bytes + whole_bytes) <= CHAIN_BLOCK_BUDGET]
    return max(fitting) if fitting else cands[0]


def chain_pallas_call(fn: Callable, layout: tuple, n_levels: int,
                      carry_pos: int, *, interpret: bool) -> Callable:
    """Build the jitted ``pallas_call`` running ``n_levels`` levels of a
    width-1 kernel-tagged chain (see
    :meth:`ExecutableCache.lookup_chain_pallas`; constants are static).

    The kernel's grid is ``(row blocks, levels)``.  The output block is the
    carry: loaded from the carry operand at level 0, resident in VMEM
    across the (innermost, sequential) level axis, and written back once
    per row block.  Each level's ``"xs"`` slice arrives as its own block,
    so only one level of a stacked operand is in VMEM at a time; hoisted
    ``"xs_const"`` scalars sit whole in SMEM.  Operands the tag lets the
    kernel row-block (:func:`_row_positions`) and whose per-level shape
    matches the carry's rank and row count are cut into ``(bm, ...)``
    blocks; the others are loaded whole.  Carries of rank < 2 are one
    block.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    const_pos = tuple(p for p, lay in enumerate(layout) if lay == "const")
    tensor_pos = tuple(p for p, lay in enumerate(layout) if lay != "const")
    row_pos = _row_positions(fn, layout)

    def chain_call(*flat):
        carry0 = flat[carry_pos]
        shape, dtype = tuple(carry0.shape), carry0.dtype

        def level_shape(p):
            s = tuple(flat[p].shape)
            return s[1:] if layout[p] in ("xs", "xs_const") else s

        blocked = frozenset()
        bm = n_blocks = 1
        if len(shape) >= 2 and carry_pos in row_pos:
            blocked = frozenset(
                p for p in row_pos
                if len(level_shape(p)) == len(shape)
                and level_shape(p)[0] == shape[0])
        if blocked:
            row_bytes = sum(math.prod(level_shape(p)[1:])
                            * flat[p].dtype.itemsize for p in blocked)
            row_bytes += math.prod(shape[1:]) * dtype.itemsize   # output
            whole_bytes = sum(math.prod(level_shape(p))
                              * flat[p].dtype.itemsize
                              for p in tensor_pos
                              if p not in blocked
                              and layout[p] != "xs_const")
            bm = _block_rows(shape[0], dtype.itemsize, row_bytes,
                             whole_bytes)
            n_blocks = shape[0] // bm

        def spec(p):
            lay = layout[p]
            if lay == "xs_const":
                return pl.BlockSpec(memory_space=pltpu.SMEM)
            s = level_shape(p)
            if p in blocked:
                block = (bm,) + s[1:]
                rest = (0,) * (len(s) - 1)
                if lay == "xs":
                    return pl.BlockSpec((None,) + block,
                                        lambda r, l: (l, r) + rest)
                return pl.BlockSpec(block, lambda r, l: (r,) + rest)
            zeros = (0,) * len(s)
            if lay == "xs":
                return pl.BlockSpec((None,) + s, lambda r, l: (l,) + zeros)
            return pl.BlockSpec(s, lambda r, l: zeros)

        def kernel(*refs):
            out_ref = refs[-1]
            ref_of = dict(zip(tensor_pos, refs))
            level = pl.program_id(1)

            @pl.when(level == 0)
            def _load_carry():
                out_ref[...] = ref_of[carry_pos][...]

            call_args = []
            for p, lay in enumerate(layout):
                if p == carry_pos:
                    call_args.append(out_ref[...])
                elif lay == "const":
                    call_args.append(flat[p])
                elif lay == "xs_const":
                    call_args.append(ref_of[p][level])
                else:                   # "single" or this level's "xs" block
                    call_args.append(ref_of[p][...])
            out = fn(*call_args)
            if isinstance(out, tuple):
                out = out[0]            # chain ops write one payload
            out_ref[...] = out

        return pl.pallas_call(
            kernel,
            grid=(n_blocks, n_levels),
            in_specs=[spec(p) for p in tensor_pos],
            out_specs=spec(carry_pos),
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=CHAIN_VMEM_LIMIT),
            interpret=interpret,
            name=f"bind_chain_{getattr(fn, '__name__', 'fn')}",
        )(*(flat[p] for p in tensor_pos))

    return jax.jit(_named(chain_call, "bind_chain", fn),
                   static_argnums=const_pos)


# Process-wide cache: signatures are shared across executors and workflows
# (the same tiled-GEMM leaf compiles once per process, not once per run).
EXEC_CACHE = ExecutableCache()


def process_local_cache() -> ExecutableCache:
    """The calling process's executable cache (per-worker instantiation).

    Pool workers of the process-pool backend resolve op bodies through
    their *own* cache: XLA executables and jit-vs-python decisions are
    process-local state that cannot ship over a pipe, and a worker must
    make exactly the decisions the serial reference would (same
    ``_build`` rules) so numerics stay bitwise-identical across backends.
    In the parent this returns :data:`EXEC_CACHE`; in a spawned worker the
    module re-imports and the fresh process-wide instance *is* the
    per-worker cache — one signature table per rank, populated on first
    replay and persistent across plans for the worker's lifetime.
    """
    return EXEC_CACHE
