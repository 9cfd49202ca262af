"""Fault-tolerant training driver.

Runs on whatever devices exist (CPU: 1-device mesh; TPU: the production
mesh) with: an ahead-of-time compiled train step (compile seconds
reported apart from step seconds), deterministic synthetic data, async
checkpointing + auto-restore, failure injection + supervisor restarts,
straggler monitoring, optional int8 gradient compression.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch spikingformer-4-256 \
      --smoke --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch h2o-danube-3-4b \
      --smoke --steps 100 --batch 8 --seq 128 --inject-failure-at 30
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import ALL_ARCHS, get_config
from repro.data import DataConfig, make_pipeline
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import registry
from repro.models.moe import use_ep_mesh
from repro.optim import adamw, compress_state_init, warmup_cosine
from repro.runtime import (FailureInjector, StragglerMonitor, TrainSupervisor,
                           SimulatedFailure)


def make_batch_fn(cfg, batch_size: int, seq_len: int):
    if cfg.family in ("spikingformer", "cifarnet"):
        data = make_pipeline(DataConfig(
            kind="images", global_batch=batch_size,
            img_size=cfg.vision.img_size, channels=cfg.vision.in_channels,
            num_classes=cfg.vocab_size))
        return data.batch_at
    data = make_pipeline(DataConfig(kind="lm", global_batch=batch_size,
                                    seq_len=seq_len,
                                    vocab_size=cfg.vocab_size))
    lm_batch = data.batch_at

    if cfg.family == "vlm":
        n, e = cfg.frontend.num_embeds, cfg.frontend.embed_dim

        def fn(step):
            b = lm_batch(step)
            rng = np.random.default_rng(step)
            b["patch_embeds"] = rng.normal(
                0, 0.02, (batch_size, n, e)).astype(np.float32)
            return b
        return fn
    if cfg.family == "encdec":
        def fn(step):
            b = lm_batch(step)
            rng = np.random.default_rng(step)
            b["audio_embeds"] = rng.normal(
                0, 0.02, (batch_size, cfg.encoder_seq,
                          cfg.d_model)).astype(np.float32)
            return b
        return fn
    return lm_batch


def train(arch: str, smoke: bool, total_steps: int, batch: int, seq: int,
          lr: float, ckpt_dir: Optional[str], ckpt_every: int,
          inject_failure_at: Optional[int], compress: bool,
          log_every: int = 10, seed: int = 0, qat: Optional[str] = None,
          dtype: Optional[str] = None, params=None,
          stats: Optional[dict] = None):
    """Train ``arch`` for ``total_steps`` steps; returns the losses.

    ``dtype`` overrides the config's activation/parameter dtype;
    ``params`` starts from the given parameters instead of a fresh init
    (they are donated to the first step). A ``stats`` dict receives
    ``compile_s`` (the train step's ahead-of-time compile), ``step_s``
    (wall seconds of every executed step, ending in a device sync) and
    ``hlo`` (the compiled step's text)."""
    cfg = get_config(arch, smoke=smoke)
    if dtype is not None:
        cfg = cfg.replace(dtype=dtype)
    stateful = cfg.family in ("spikingformer", "cifarnet")
    mesh = make_host_mesh()
    opt = adamw(warmup_cosine(lr, max(1, total_steps // 20), total_steps))
    batch_fn = make_batch_fn(cfg, batch, seq)
    train_step = steps_lib.build_train_step(cfg, opt, compress=compress,
                                            qat=qat)
    jitted = jax.jit(train_step, donate_argnums=(0, 1))

    if params is None:
        params = registry.init(cfg, jax.random.PRNGKey(seed))
    opt_state = opt.init(params)
    if compress:
        opt_state["compress_err"] = compress_state_init(params)
    model_state = registry.init_state(cfg)
    n_params = sum(np.prod(l.shape) for l in
                   jax.tree_util.tree_leaves(params))
    print(f"[train] {cfg.name} ({'smoke' if smoke else 'full'}): "
          f"{n_params/1e6:.2f}M params, {total_steps} steps, "
          f"batch={batch} seq={seq}")
    args = (params, opt_state, jnp.asarray(0, jnp.int32),
            {k: jnp.asarray(v) for k, v in batch_fn(0).items()})
    t0 = time.perf_counter()
    step_fn = jitted.lower(*args, *((model_state,) if stateful else ())
                           ).compile()
    compile_s = time.perf_counter() - t0
    print(f"[train] train step compiled in {compile_s:.2f}s on "
          f"{jax.devices()[0].platform} ({jax.devices()[0].device_kind})")
    step_times = []
    if stats is not None:
        stats.update(compile_s=compile_s, step_s=step_times,
                     hlo=step_fn.as_text())

    cm = CheckpointManager(ckpt_dir) if ckpt_dir else None
    injector = FailureInjector(failure_steps=[inject_failure_at]
                               if inject_failure_at else [])
    monitor = StragglerMonitor(
        on_straggler=lambda r: print(
            f"[straggler] step {r.step}: {r.seconds*1e3:.0f} ms"))
    supervisor = TrainSupervisor(max_restarts=3)
    losses = []

    def run_segment(start_step: int) -> int:
        nonlocal params, opt_state, model_state
        if cm is not None and cm.latest_step() is not None:
            tmpl = {"params": params, "opt": opt_state}
            if stateful:
                tmpl["model_state"] = model_state
            tree, ck_step, _ = cm.restore(tmpl)
            params, opt_state = tree["params"], tree["opt"]
            if stateful:
                model_state = tree["model_state"]
            start_step = ck_step
            print(f"[train] restored checkpoint @ step {ck_step}")
        step_arr = jnp.asarray(start_step, jnp.int32)
        step = start_step
        while step < total_steps:
            injector.maybe_fail(step)
            b = {k: jnp.asarray(v) for k, v in batch_fn(step).items()}
            t0 = time.perf_counter()
            if stateful:
                params, opt_state, step_arr, metrics, model_state = step_fn(
                    params, opt_state, step_arr, b, model_state)
            else:
                params, opt_state, step_arr, metrics = step_fn(
                    params, opt_state, step_arr, b)
            loss = float(metrics["loss"])
            step_times.append(time.perf_counter() - t0)
            monitor.observe(step, step_times[-1])
            losses.append(loss)
            if step % log_every == 0 or step == total_steps - 1:
                extra = f" fire={float(metrics['fire_rate']):.3f}" \
                    if "fire_rate" in metrics else ""
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}{extra}")
            step += 1
            if cm is not None and step % ckpt_every == 0:
                tree = {"params": params, "opt": opt_state}
                if stateful:
                    tree["model_state"] = model_state
                cm.save(step, tree)
        if cm is not None:
            tree = {"params": params, "opt": opt_state}
            if stateful:
                tree["model_state"] = model_state
            cm.save(total_steps, tree, blocking=True)
        return step

    final = supervisor.run(run_segment, 0, total_steps)
    if supervisor.restarts:
        print(f"[train] survived {len(supervisor.restarts)} restart(s): "
              f"{supervisor.restarts}")
    if monitor.straggler_steps:
        print(f"[train] straggler steps flagged: {monitor.straggler_steps}")
    print(f"[train] done @ step {final}; first loss {losses[0]:.4f} "
          f"last loss {losses[-1]:.4f}")
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ALL_ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--inject-failure-at", type=int, default=None)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--qat", default=None, choices=["int8", "int4"],
                    help="quantization-aware training: the loss sees "
                         "fake-quantized linears (STE grads to fp32 "
                         "masters; repro.quant.qat)")
    args = ap.parse_args()
    setup_compile_cache()
    train(args.arch, args.smoke, args.steps, args.batch, args.seq, args.lr,
          args.ckpt_dir, args.ckpt_every, args.inject_failure_at,
          args.compress_grads, qat=args.qat)


if __name__ == "__main__":
    main()
