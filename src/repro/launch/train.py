"""Training driver: data pipeline -> jit train_step -> checkpoint/restart,
straggler monitoring, failure injection, optional EF-int8 grad compression.

Runs anywhere: single CPU (smoke/examples) up to the production mesh (the
same step function is what dryrun.py lowers for 512 chips).

  PYTHONPATH=src python -m repro.launch.train --arch llama32_1b --smoke \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import registry
from repro.data import DataConfig, SyntheticLM
from repro.ft import FailureInjector, StragglerMonitor
from repro.launch import steps as step_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw
from repro.optim.compress import ef_int8_state


def train(
    arch: str = "llama32_1b",
    smoke: bool = True,
    steps: int = 100,
    batch: int = 8,
    seq: int = 128,
    lr: float = 1e-3,
    ckpt_dir: str = "",
    ckpt_every: int = 50,
    log_every: int = 10,
    grad_compress: bool = False,
    fail_at_step: int = -1,
    seed: int = 0,
    d_model: int = 0,
    n_layers: int = 0,
):
    cfg = registry.get_config(arch, smoke=smoke)
    overrides = {}
    if d_model:
        overrides["d_model"] = d_model
    if n_layers:
        overrides["n_layers"] = n_layers
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    opt_cfg = AdamWConfig(lr=lr, warmup_steps=min(20, steps // 5 + 1),
                          total_steps=steps)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                  global_batch=batch, seed=seed))
    train_step = jax.jit(
        step_lib.make_train_step(cfg, opt_cfg, grad_compress=grad_compress),
        donate_argnums=(0, 1),
    )

    mgr = CheckpointManager(ckpt_dir, keep=2) if ckpt_dir else None
    injector = FailureInjector(fail_at_step if fail_at_step >= 0 else None)
    monitor = StragglerMonitor()

    start = 0
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    opt_state = adamw.init_state(params, opt_cfg)
    ef = ef_int8_state(params) if grad_compress else None
    if mgr is not None and mgr.latest_step() is not None:
        start = mgr.latest_step()
        state = mgr.restore(start, {"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        print(f"[resume] restored step {start} from {ckpt_dir}")

    losses = []
    for step in range(start, steps):
        injector.maybe_fail(step)
        t0 = time.time()
        b = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        if grad_compress:
            params, opt_state, ef, metrics = train_step(params, opt_state, b, ef)
        else:
            params, opt_state, metrics = train_step(params, opt_state, b)
        loss = float(metrics["loss"])
        losses.append(loss)
        monitor.observe(step, time.time() - t0)
        if log_every and step % log_every == 0:
            print(
                f"step {step:5d} loss {loss:7.4f} "
                f"gnorm {float(metrics['grad_norm']):8.3f} "
                f"lr {float(metrics['lr']):.2e} ({time.time()-t0:.2f}s)"
            )
        if mgr is not None and ckpt_every and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, {"params": params, "opt": opt_state},
                     {"loss": loss})
    if mgr is not None:
        mgr.save(steps, {"params": params, "opt": opt_state},
                 {"loss": losses[-1] if losses else float("nan")})
        mgr.wait()
    if monitor.events:
        print(f"[stragglers] {len(monitor.events)} flagged steps")
    return np.array(losses)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama32_1b")
    ap.add_argument("--full", action="store_true", help="full (non-smoke) config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--fail-at-step", type=int, default=-1)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--n-layers", type=int, default=0)
    args = ap.parse_args()
    train(
        arch=args.arch, smoke=not args.full, steps=args.steps,
        batch=args.batch, seq=args.seq, lr=args.lr, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, log_every=args.log_every,
        grad_compress=args.grad_compress, fail_at_step=args.fail_at_step,
        d_model=args.d_model, n_layers=args.n_layers,
    )


if __name__ == "__main__":
    main()
