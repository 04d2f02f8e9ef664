"""End-to-end training driver with checkpoint/restart + heartbeat.

    PYTHONPATH=src python -m repro.launch.train \
        --arch gemma_7b --reduced --steps 200 --batch 8 --seq 64 \
        --ckpt-dir /tmp/run1 --resume auto

Argument parsing happens *before* jax import so ``--fake-devices`` can set
XLA_FLAGS (used by the multi-device integration tests).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", choices=("auto", "never"), default="auto")
    ap.add_argument("--heartbeat", default=None)
    ap.add_argument("--log-file", default=None)
    ap.add_argument("--fake-devices", type=int, default=0)
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model-axis size when fake devices are used")
    ap.add_argument("--grad-sync", default="implicit",
                    choices=("implicit", "tree", "ring", "hierarchical"))
    ap.add_argument("--crash-at-step", type=int, default=None,
                    help="fault-injection hook for the integration test")
    ap.add_argument("--metrics-out", default=None)
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = parse_args(argv)
    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.fake_devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import configs
    from repro.models import LanguageModel
    from repro.optim import AdamW, warmup_cosine
    from repro.data import SyntheticLMDataset
    from repro.ckpt import CheckpointManager
    from repro.train.step import make_train_step, make_manual_dp_train_step
    from repro.runtime.supervisor import touch_heartbeat
    from repro.launch.mesh import make_host_mesh
    from repro.launch.compile_cache import enable_compile_cache
    from repro.sharding import make_policy

    enable_compile_cache()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = LanguageModel(cfg)
    optimizer = AdamW(
        learning_rate=warmup_cosine(args.lr, args.warmup, args.steps))

    data = SyntheticLMDataset(
        vocab_size=cfg.vocab_size, seq_len=args.seq,
        global_batch=args.batch, seed=args.seed,
        enc_len=(args.seq // cfg.encoder_ratio if cfg.encoder_layers else 0),
        d_model=cfg.d_model if (cfg.encoder_layers or cfg.frontend) else 0,
        vision_tokens=cfg.vision_tokens if cfg.frontend == "vision" else 0,
    )

    params = model.init(jax.random.PRNGKey(args.seed))
    opt_state = optimizer.init(params)

    n_dev = len(jax.devices())
    policy = None
    manual_step = None
    if args.grad_sync != "implicit" and n_dev > 1:
        mesh = make_host_mesh(n_data=n_dev)
        manual_step = make_manual_dp_train_step(
            model, optimizer, mesh, schedule=args.grad_sync)
        from repro.train.step import init_error_state
        err = init_error_state(params)
    elif n_dev > 1:
        mesh = make_host_mesh(
            n_data=n_dev // args.mesh_model, n_model=args.mesh_model)
        policy = make_policy(mesh)
    step_fn = make_train_step(model, optimizer, policy) \
        if manual_step is None else None

    start_step = 0
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt and args.resume == "auto" and ckpt.latest_step() is not None:
        (params, opt_state), extra = ckpt.restore((params, opt_state))
        start_step = int(extra["step"]) + 1
        print(f"[train] resumed from step {start_step - 1}", flush=True)

    log_f = open(args.log_file, "a") if args.log_file else None
    final_metrics = {}
    for step in range(start_step, args.steps):
        if args.crash_at_step is not None and step == args.crash_at_step:
            print(f"[train] injected crash at step {step}", flush=True)
            os._exit(42)
        batch = data.batch_at(step)
        if manual_step is not None:
            params, opt_state, loss, err = manual_step(
                params, opt_state, batch, err)
            metrics = {"loss": loss}
        else:
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        if args.heartbeat:
            touch_heartbeat(args.heartbeat)
        if ckpt and args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step, (params, opt_state), extra={"step": step})
        if step % 10 == 0 or step == args.steps - 1:
            final_metrics = {
                k: float(v) for k, v in metrics.items()
                if hasattr(v, "shape") or isinstance(v, (int, float))}
            line = json.dumps({"step": step, **final_metrics})
            print(f"[train] {line}", flush=True)
            if log_f:
                log_f.write(line + "\n")
                log_f.flush()
    if ckpt:
        ckpt.save(args.steps - 1, (params, opt_state),
                  extra={"step": args.steps - 1}, block=True)
        ckpt.wait()
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump({"final": final_metrics}, f)
    print("[train] done", flush=True)


if __name__ == "__main__":
    main()
