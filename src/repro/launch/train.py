"""Training launcher: sharded train loop with checkpoint/resume.

On this CPU container it runs reduced configs end-to-end (the e2e example
drivers use it); on a real pod the same entry point scales — mesh and
shardings come from the same code path the dry-run validates.

    PYTHONPATH=src python -m repro.launch.train --arch minitron-8b \
        --smoke --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ck --resume
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.models import api
from repro.train import checkpoint as ckpt_lib
from repro.train import data as data_lib
from repro.train import optimizer as opt_lib
from repro.train import train_step as ts


def make_mesh_for(n_devices: int):
    import math

    d = int(math.sqrt(n_devices))
    while n_devices % d:
        d -= 1
    return jax.make_mesh((d, n_devices // d), ("data", "model"))


def train_loop(cfg, steps: int, global_batch: int, seq_len: int,
               ckpt_dir: str | None = None, resume: bool = False,
               microbatches: int = 1, log_every: int = 1,
               save_every: int = 50, host: int = 0, n_hosts: int = 1):
    mesh = make_mesh_for(jax.device_count())
    ocfg = opt_lib.OptConfig(warmup_steps=min(10, steps // 5 + 1),
                             total_steps=steps)
    dcfg = data_lib.DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                               global_batch=global_batch, n_hosts=n_hosts)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    state = opt_lib.init_opt_state(params, ocfg)
    start_step = 0
    if resume and ckpt_dir:
        ckpt_lib.clean_tmp(ckpt_dir)
        restored, at = ckpt_lib.restore_latest(
            ckpt_dir, {"params": params, "opt": state})
        if at >= 0:
            params, state = restored["params"], restored["opt"]
            start_step = at
            print(f"[train] resumed from step {at}")

    batch0 = {k: jnp.asarray(v)
              for k, v in data_lib.global_batch(dcfg, 0).items()}
    extra = {}
    if cfg.family == "audio":
        extra["frame_embeds"] = jnp.zeros(
            (global_batch, cfg.n_audio_ctx, cfg.d_model), jnp.float32)
    if cfg.family == "vlm":
        extra["patch_embeds"] = jnp.zeros(
            (global_batch, cfg.n_patches, cfg.d_model), jnp.float32)
    batch0.update(extra)

    with mesh:
        step_fn = ts.make_train_step(cfg, ocfg, mesh,
                                     microbatches=microbatches)
        in_sh, out_sh = ts.shardings_for_train(mesh, params, state, batch0)
        params = jax.device_put(params, in_sh[0])
        state = jax.device_put(state, in_sh[1])
        fn = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh)
        losses = []
        for s in range(start_step, steps):
            batch = {k: jnp.asarray(v)
                     for k, v in data_lib.global_batch(dcfg, s).items()}
            batch.update(extra)
            t0 = time.perf_counter()
            params, state, metrics = fn(params, state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if s % log_every == 0:
                print(f"[train] step {s} loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"dt={time.perf_counter() - t0:.2f}s", flush=True)
            if ckpt_dir and (s + 1) % save_every == 0:
                ckpt_lib.save(ckpt_dir, s + 1, {"params": params,
                                                "opt": state})
                ckpt_lib.keep_last(ckpt_dir, 3)
        if ckpt_dir:
            ckpt_lib.save(ckpt_dir, steps, {"params": params, "opt": state})
    return params, losses


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    _, losses = train_loop(cfg, args.steps, args.batch, args.seq,
                           ckpt_dir=args.ckpt_dir, resume=args.resume,
                           microbatches=args.microbatches)
    print(f"[train] done: first loss {losses[0]:.4f} → last {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
