#!/usr/bin/env python3
"""Chip smoke test: the Bind engine's device path on a TPU, end to end.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the four-chip path only

One chip runs, in order: Listing 1 (the paper's distributed GEMM) at
N = 8192 f32 on device-resident tiles through ``backend="mesh"``; two
kernel-tagged chains lowered to one compiled ``pallas_call`` each; and a
``ServingRuntime`` answering 8 sessions.  ``--chips 4`` runs Listing 1 with
its ranks placed on the four chips (ships as collectives) and the
``shard_map`` GEMM, each against a plain ``jnp.dot`` reference.  Every
result is checked; any failure raises.  The last line of standard output
is one JSON object naming the device.

The script refuses to run without a TPU.  ``--cpu-rehearsal`` (for testing
the script itself) runs the same phases at tiny sizes on the CPU with the
Pallas kernels interpreted.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Sizes:
    n: int              # Listing 1: N x N f32 operands
    ib: int             # Listing 1: tile edge
    tile: int           # chain state / tile edge
    scan_levels: int
    gemm_levels: int
    state: int          # serving: per-session state edge
    sessions: int
    steps: int


CHIP = Sizes(n=8192, ib=1024, tile=1024, scan_levels=64, gemm_levels=8,
             state=2048, sessions=8, steps=3)
REHEARSAL = Sizes(n=256, ib=64, tile=128, scan_levels=8, gemm_levels=4,
                  state=128, sessions=8, steps=3)

# f32 matmuls at the TPU's default precision take one bf16 pass: the unit
# roundoff is 2^-8 and Gaussian operands give a relative Frobenius error of
# about 0.8 * 2^-8 = 3e-3.  Results are checked against precision=HIGHEST
# at 1e-2, about 2.6 * 2^-8.
MATMUL_RTOL = 1e-2
# elementwise f32 chains (64 levels of y <- a*y + x): each level rounds at
# 2^-24, so 64 levels stay well inside 1e-5 relative
EWISE_RTOL = 1e-5
# serving steps use precision=HIGHEST in the op and the reference, so only
# the batched vs unbatched accumulation order differs
SERVE_RTOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, ref) -> float:
    import jax.numpy as jnp
    return float(jnp.linalg.norm((got - ref).ravel())
                 / jnp.linalg.norm(ref.ravel()))


def check(name: str, err: float, tol: float) -> None:
    log(f"  {name}: rel. Frobenius error {err!r} (tolerance {tol!r})")
    if not err <= tol:
        raise AssertionError(f"{name}: error {err!r} above {tol!r}")


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# -- phases -------------------------------------------------------------------

def phase_listing1(sz: Sizes, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.backends.mesh import MeshBackend
    from repro.core.executable_cache import EXEC_CACHE
    from repro.linalg.distributed import run_distributed_gemm

    log(f"[listing1] N={sz.n} ib={sz.ib} ({sz.n // sz.ib}x{sz.n // sz.ib} "
        f"tiles) NP=NQ=2 (4 ranks on one device) backend=mesh f32 "
        f"matmul precision=default")
    ka, kb = jax.random.split(jax.random.key(seed))
    A = jax.random.normal(ka, (sz.n, sz.n), jnp.float32)
    B = jax.random.normal(kb, (sz.n, sz.n), jnp.float32)
    compiles0, fallbacks0 = EXEC_CACHE.compiles, EXEC_CACHE.fallbacks
    walls = []
    for _ in range(2):             # the first run compiles, the second is warm
        mb = MeshBackend()
        t0 = time.perf_counter()
        C, stats, _ = run_distributed_gemm(A, B, ib=sz.ib, NP=2, NQ=2,
                                           backend=mb)
        C.block_until_ready()
        walls.append(time.perf_counter() - t0)
    if not isinstance(C, jax.Array):
        raise AssertionError(f"Listing 1 returned {type(C)}, not a device "
                             "array")
    compiles = EXEC_CACHE.compiles - compiles0
    fallbacks = EXEC_CACHE.fallbacks - fallbacks0
    log(f"  ops={stats.ops_executed} wavefronts={len(stats.wavefronts)} "
        f"batches={mb.batches_dispatched} ops_fused={mb.ops_fused} "
        f"chains={mb.chains_dispatched} ops_chained={mb.ops_chained} "
        f"pallas_chains={mb.pallas_chains_dispatched}")
    log(f"  ExecutableCache compiles={compiles} fallbacks={fallbacks}")
    if fallbacks or not compiles:
        raise AssertionError("Listing 1 left the device path: "
                             f"compiles={compiles} fallbacks={fallbacks}")
    ref = jnp.dot(A, B, precision=jax.lax.Precision.HIGHEST)
    check("C vs jnp.dot(precision=HIGHEST)", rel_err(C, ref), MATMUL_RTOL)
    rows = np.array([0, sz.n // 3, sz.n // 2 + 1, sz.n - 1])
    ref64 = (np.asarray(A[rows], np.float64)
             @ np.asarray(B, np.float64))
    got = np.asarray(C[rows], np.float64)
    err64 = float(np.linalg.norm(got - ref64) / np.linalg.norm(ref64))
    check(f"C rows {rows.tolist()} vs float64 NumPy", err64, MATMUL_RTOL)
    log(f"  wall (one observation each): cold {walls[0]!r} s, "
        f"warm {walls[1]!r} s")
    log(f"  peak_bytes_in_use {peak_bytes(jax.devices()[0])!r}")


def _chain(fn, carry, exteriors, backend):
    """Record ``carry <- fn(carry, *exteriors[l])`` for every level ``l``
    on one rank and return the final carry."""
    from repro import core as bind
    ex = bind.LocalExecutor(1, backend=backend)
    with bind.Workflow(n_nodes=1, executor=ex) as wf:
        c = wf.array(carry, "carry")
        for level in exteriors:
            wf.call(fn, (c,) + tuple(wf.array(x) for x in level),
                    name=fn.__name__)
        return wf.fetch(c)


def phase_chains(sz: Sizes, seed: int, pallas="auto") -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.backends.mesh import MeshBackend
    from repro.core.executable_cache import EXEC_CACHE
    from repro.kernels.gemm.ops import gemm_tile
    from repro.kernels.linear_scan.ops import scan_step

    t = sz.tile
    k = jax.random.split(jax.random.key(seed + 1), 5)
    a = jax.random.uniform(k[0], (sz.scan_levels, t, t), jnp.float32,
                           0.5, 1.0)
    x = jax.random.normal(k[1], (sz.scan_levels, t, t), jnp.float32)
    y0 = jax.random.normal(k[2], (t, t), jnp.float32)
    ga = jax.random.normal(k[3], (sz.gemm_levels, t, t), jnp.float32)
    gb = jax.random.normal(k[4], (sz.gemm_levels, t, t), jnp.float32)
    c0 = jnp.zeros((t, t), jnp.float32)
    cases = [
        ("scan_step", scan_step, y0,
         [(a[i], x[i]) for i in range(sz.scan_levels)]),
        ("gemm_tile", gemm_tile, c0,
         [(ga[i], gb[i]) for i in range(sz.gemm_levels)]),
    ]
    for name, fn, carry, levels in cases:
        mb = MeshBackend(pallas=pallas)
        compiles0 = EXEC_CACHE.compiles
        out = _chain(fn, carry, levels, mb)
        compiles = EXEC_CACHE.compiles - compiles0
        serial = _chain(fn, carry, levels, "serial")
        n_levels = len(levels)
        log(f"[chain] {name} {t}x{t} f32, {n_levels} levels: "
            f"pallas_chains={mb.pallas_chains_dispatched} "
            f"ops_pallas={mb.ops_pallas} interpret={mb.interpret} "
            f"compiles={compiles}")
        if (mb.pallas_chains_dispatched < 1 or mb.ops_pallas != n_levels
                or mb.chains_dispatched != mb.pallas_chains_dispatched):
            raise AssertionError(f"{name}: the chain did not run as one "
                                 "Pallas kernel")
        if mb.interpret and jax.devices()[0].platform == "tpu":
            raise AssertionError(f"{name}: Pallas interpreted on a TPU")
        bitwise = bool(jnp.array_equal(out, serial))
        log(f"  pallas vs serial backend: bitwise equal={bitwise}, "
            f"rel. Frobenius difference {rel_err(out, serial)!r}")
        if fn is scan_step:
            check("pallas vs serial", rel_err(out, serial), EWISE_RTOL)
        else:
            ref = c0 + jnp.einsum("lij,ljk->ik", ga, gb,
                                  precision=jax.lax.Precision.HIGHEST)
            check("pallas vs precision=HIGHEST", rel_err(out, ref),
                  MATMUL_RTOL)
            check("serial vs precision=HIGHEST", rel_err(serial, ref),
                  MATMUL_RTOL)


def phase_serving(sz: Sizes, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro import core as bind
    from repro.serve import ServingRuntime

    hi = jax.lax.Precision.HIGHEST

    @bind.op
    def serve_step(x: bind.InOut, w: bind.In):
        return x + 0.01 * jnp.tanh(jnp.dot(x, w, precision=hi))

    ref_step = jax.jit(lambda x, w: x + 0.01 * jnp.tanh(
        jnp.dot(x, w, precision=hi)))
    kw, kx = jax.random.split(jax.random.key(seed + 2))
    W = jax.random.normal(kw, (sz.state, sz.state), jnp.float32) / sz.state
    X0 = jax.random.normal(kx, (sz.sessions, sz.state, sz.state),
                           jnp.float32)
    shared = {}
    answered = 0
    with ServingRuntime(backend="mesh", admission_window=0.01) as rt:
        sessions = [rt.session() for _ in range(sz.sessions)]

        def init_weight(s):
            shared["w"] = s.array(W, name="w")

        sessions[0].submit(init_weight).result(timeout=600)
        inits = []
        for i, sess in enumerate(sessions):
            def init(s, x0=X0[i]):
                s.state["x"] = s.array(x0, name="x")
            inits.append(sess.submit(init))
        for f in inits:
            f.result(timeout=600)

        def step(s):
            serve_step(s.state["x"], shared["w"])
            return s.state["x"]

        refs = list(X0)
        worst = 0.0
        for k in range(sz.steps):
            futs = [sess.submit(step) for sess in sessions]
            refs = [ref_step(r, W) for r in refs]
            for i, f in enumerate(futs):
                got = f.result(timeout=600)
                worst = max(worst, rel_err(got, refs[i]))
                answered += 1
        mb = rt.executor.backend
        m = rt.metrics
        log(f"[serving] {sz.sessions} sessions x {sz.steps} steps, state "
            f"{sz.state}x{sz.state} f32, shared weight: answered={answered} "
            f"completed={m.requests_completed} failed={m.requests_failed} "
            f"batched_flushes={m.batched_flushes} "
            f"fused_batches={mb.batches_dispatched}")
    if answered != sz.sessions * sz.steps or m.requests_failed:
        raise AssertionError("serving left requests unanswered")
    check("worst answer vs plain jnp steps", worst, SERVE_RTOL)


def phase_four_chips(sz: Sizes, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro import core as bind
    from repro.core.backends.mesh import MeshBackend
    from repro.linalg.distributed import (distributed_gemm_listing1,
                                          distributed_gemm_shardmap,
                                          make_distributed_inputs)

    devices = jax.devices()
    if len(devices) < 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")
    ka, kb = jax.random.split(jax.random.key(seed))
    A = jax.random.normal(ka, (sz.n, sz.n), jnp.float32)
    B = jax.random.normal(kb, (sz.n, sz.n), jnp.float32)
    ref = jnp.dot(A, B, precision=jax.lax.Precision.HIGHEST)

    log(f"[listing1 x4] N={sz.n} ib={sz.ib} NP=NQ=2, rank r on "
        f"devices[r], backend=mesh")
    mb = MeshBackend()
    ex = bind.LocalExecutor(4, backend=mb)
    t0 = time.perf_counter()
    with bind.Workflow(n_nodes=4, executor=ex) as wf:
        a, b, c = make_distributed_inputs(wf, A, B, ib=sz.ib, NP=2, NQ=2)
        distributed_gemm_listing1(wf, a, b, c, 2, 2)
        C = c.to_array()
        C.block_until_ready()
        wall = time.perf_counter() - t0
        misplaced = mb.misplaced(ex)
        held = [len(ex._stores[r]) for r in range(4)]
    log(f"  ships_lowered={mb.ships_lowered} "
        f"ships_simulated={mb.ships_simulated} schedule={mb._schedule_eff} "
        f"payloads per rank={held} misplaced={len(misplaced)}")
    log(f"  wall (one observation, cold): {wall!r} s")
    if not mb.ships_lowered or mb.ships_simulated:
        raise AssertionError("ships did not all run as collectives")
    if misplaced or not all(held):
        raise AssertionError(f"payloads off their rank's device: "
                             f"{misplaced[:8]}")
    check("C vs jnp.dot(precision=HIGHEST)", rel_err(C, ref), MATMUL_RTOL)

    mesh = jax.make_mesh((2, 2), ("p", "q"), devices=devices[:4])
    for schedule in ("tree", "ring"):
        out = distributed_gemm_shardmap(mesh, schedule=schedule)(A, B)
        log(f"[shard_map gemm] (2, 2) mesh, schedule={schedule}")
        check("C vs jnp.dot(precision=HIGHEST)", rel_err(out, ref),
              MATMUL_RTOL)
    for i, dev in enumerate(devices[:4]):
        log(f"  device {i} peak_bytes_in_use {peak_bytes(dev)!r}")


# -- driver -------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, Pallas interpreted "
                         "(tests the script, measures nothing)")
    args = ap.parse_args(argv)
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                "--xla_force_host_platform_device_count=4 "
                + os.environ.get("XLA_FLAGS", ""))
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    dev = jax.devices()[0]
    want = "cpu" if args.cpu_rehearsal else "tpu"
    if dev.platform != want:
        print(f"chip_smoke: needs a {want.upper()}, JAX found "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__} "
        f"compile cache={enable_compile_cache()}")
    sizes = REHEARSAL if args.cpu_rehearsal else CHIP
    if args.chips == 4:
        phase_four_chips(sizes, args.seed)
    else:
        phase_listing1(sizes, args.seed)
        # off the TPU one device does not arm chain lowering by itself
        phase_chains(sizes, args.seed,
                     pallas=True if args.cpu_rehearsal else "auto")
        phase_serving(sizes, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
