"""Serving launcher: batched autoregressive decode with a sharded cache.

    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \
        --batch 4 --prompt-len 16 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.train import make_mesh_for
from repro.models import api
from repro.train import train_step as ts


def generate(cfg, batch: int, prompt_len: int, gen: int, max_len: int = 0,
             greedy: bool = True, seed: int = 0):
    """Prefill via teacher-forced decode steps, then generate ``gen`` tokens."""
    mesh = make_mesh_for(jax.device_count())
    max_len = max_len or (prompt_len + gen)
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, cfg.vocab, (batch, prompt_len)).astype(np.int32)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    cache = api.init_cache(cfg, batch, max_len, dtype=jnp.float32)
    if cfg.family == "audio":
        from repro.models import whisper

        frame = jnp.zeros((batch, cfg.n_audio_ctx, cfg.d_model), jnp.float32)
        enc_out = whisper.encode(params, frame, cfg)
        xk, xv = whisper.enc_kv(params, enc_out, cfg)
        cache["xk"] = xk.astype(cache["xk"].dtype)
        cache["xv"] = xv.astype(cache["xv"].dtype)

    with mesh:
        serve_step = ts.make_serve_step(cfg, mesh)
        fn = jax.jit(serve_step)
        toks = jnp.asarray(prompt)
        out_tokens = []
        t0 = time.perf_counter()
        lg = None
        for t in range(prompt_len + gen - 1):
            if t < prompt_len:
                tok = toks[:, t : t + 1]
            else:
                tok = out_tokens[-1]
            lg, cache = fn(params, cache, tok, jnp.int32(t + 1))
            if t >= prompt_len - 1:
                if cfg.serve_sample:
                    nxt = lg  # serve_step already returned sampled tokens
                elif greedy:
                    nxt = jnp.argmax(lg[:, -1], axis=-1).astype(
                        jnp.int32)[:, None]
                else:
                    nxt = jnp.asarray(
                        rng.integers(0, cfg.vocab, (batch, 1)), jnp.int32)
                out_tokens.append(nxt)
        dt = time.perf_counter() - t0
    gen_arr = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    return gen_arr, dt


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    toks, dt = generate(cfg, args.batch, args.prompt_len, args.gen)
    n = toks.shape[0] * toks.shape[1]
    print(f"[serve] generated {toks.shape} tokens in {dt:.2f}s "
          f"({n / dt:.1f} tok/s); sample: {toks[0][:8].tolist()}")


if __name__ == "__main__":
    main()
