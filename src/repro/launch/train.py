"""End-to-end training driver (CPU-runnable; pjit on real hardware).

``python -m repro.launch.train --arch smollm-135m --smoke --steps 120``
trains the reduced config with the full production loop: deterministic
sharded data pipeline, AdamW + cosine schedule, checkpoint/restart,
simulated transient failure, straggler watermarks.  Drop ``--smoke`` for
the real ~135M-parameter config (slow on this 1-core container; the
production path is the same code under a mesh).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.data import SyntheticLM
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.optim import AdamW
from repro.train.runner import RunnerConfig, TrainRunner
from repro.train.steps import make_train_step


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="checkpoints/train_demo")
    ap.add_argument("--ckpt-every", type=int, default=40)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    print(f"[train] {cfg.name}: ~{cfg.approx_params()/1e6:.1f}M params")
    params = M.init(cfg, jax.random.PRNGKey(0))
    optim = AdamW()
    opt_state = optim.init(params)
    step_fn = jax.jit(make_train_step(cfg, optim, remat=False),
                      donate_argnums=(0, 1))
    data = SyntheticLM(seed=0, global_batch=args.batch, seq_len=args.seq,
                       vocab=cfg.vocab)

    rc = RunnerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, fail_at=tuple(args.fail_at))
    runner = TrainRunner(rc, step_fn, params, opt_state, data)
    out = runner.run()
    losses = [m["loss"] for m in out["metrics"]]
    print(f"[train] steps={len(losses)} loss {losses[0]:.3f} → {losses[-1]:.3f} "
          f"mean_step={out['mean_step_s']*1e3:.0f}ms "
          f"stragglers={out['stragglers']}")
    assert losses[-1] < losses[0], "loss must decrease"


if __name__ == "__main__":
    main()
