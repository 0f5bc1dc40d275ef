"""Serving driver: batched greedy decoding with KV caches; the long-context
path uses the mqr-KV sparse attention (the paper's technique).

  PYTHONPATH=src python -m repro.launch.serve --arch llama32_1b \
      --batch 4 --prompt-len 32 --gen 32

NOT the spatial serving front end: this module serves transformer
tokens.  Spatial query serving is :mod:`repro.serve` (front end:
batching / admission / tenants) over :mod:`repro.launch.spatial_serve`
(the per-index engine).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch import steps as step_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T


def serve(
    arch: str = "llama32_1b",
    smoke: bool = True,
    batch: int = 4,
    prompt_len: int = 32,
    gen: int = 32,
    mqr_sparse: bool = False,
    seed: int = 0,
    params=None,
    prompts=None,
):
    cfg = registry.get_config(arch, smoke=smoke)
    if params is None:
        params = T.init_params(jax.random.PRNGKey(seed), cfg)
    max_len = prompt_len + gen
    if cfg.mqr_block and mqr_sparse:
        max_len = ((max_len + cfg.mqr_block - 1) // cfg.mqr_block) * cfg.mqr_block
    if prompts is None:
        shape = (
            (batch, prompt_len, cfg.n_codebooks)
            if cfg.frontend == "audio_codebooks"
            else (batch, prompt_len)
        )
        prompts = jax.random.randint(
            jax.random.PRNGKey(seed + 1), shape, 0, cfg.vocab_size, jnp.int32
        )

    serve_step = jax.jit(
        step_lib.make_serve_step(cfg, mqr_sparse=mqr_sparse),
        donate_argnums=(2,),
        static_argnames=(),
    )
    caches = T.init_caches(cfg, batch, max_len)

    # Prefill by streaming the prompt through decode steps (exact, cache-
    # building); a chunked prefill kernel is the production TPU path.
    t0 = time.time()
    for t in range(prompt_len):
        nxt, caches = serve_step(params, prompts[:, t : t + 1], caches, t)
    generated = [nxt]
    for t in range(prompt_len, prompt_len + gen - 1):
        nxt, caches = serve_step(params, generated[-1], caches, t)
        generated.append(nxt)
    out = jnp.concatenate(generated, axis=1)
    dt = time.time() - t0
    n_tok = batch * (prompt_len + gen)
    print(
        f"[serve] {arch} batch={batch} prompt={prompt_len} gen={gen} "
        f"mqr_sparse={mqr_sparse}: {n_tok / dt:.1f} tok/s ({dt:.2f}s)"
    )
    assert bool(jnp.all(out >= 0)) and bool(jnp.all(out < cfg.vocab_size))
    return np.asarray(out)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama32_1b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mqr-sparse", action="store_true")
    args = ap.parse_args()
    serve(
        arch=args.arch, smoke=not args.full, batch=args.batch,
        prompt_len=args.prompt_len, gen=args.gen, mqr_sparse=args.mqr_sparse,
    )


if __name__ == "__main__":
    main()
