"""End-to-end training driver (the paper's full three-stage flow at LM
scale, with production fault-tolerance).

Runnable on this CPU container with smoke configs::

    PYTHONPATH=src python -m repro.launch.train --arch smoke:olmo-1b \
        --steps 50 --batch 8 --seq 64

Features (DESIGN §5):
* periodic + SIGTERM-preemption checkpoints, auto-resume from latest;
* mesh-independent checkpoints → elastic restart on a different device
  count;
* SMD data sampling (the paper's iteration-skip knob, α_D);
* per-step wall-clock deadline with skip-and-log (straggler mitigation);
* multi-level sparsity flags (α_W feedback / α_C column sampling);
* optional int8 error-feedback gradient compression for the DP
  all-reduce (--compress-grads; shard_map path, multi-device meshes).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, smoke_config
from ..core.sparsity import SparsityConfig, smd_keep_iteration
from ..checkpoint import CheckpointManager
from ..compile_cache import enable_compile_cache
from ..data import lm_batch
from ..optim.optimizers import AdamWConfig
from ..optim.schedules import linear_warmup_cosine
from .steps import build_update_step, init_train_state


def parse_arch(name: str):
    if name.startswith("smoke:"):
        return smoke_config(name.split(":", 1)[1])
    return get_config(name)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="arch id, or smoke:<id> for the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--alpha-w", type=float, default=1.0)
    ap.add_argument("--alpha-c", type=float, default=1.0)
    ap.add_argument("--alpha-d", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-step deadline; late steps are logged")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = parse_arch(args.arch)
    scfg = SparsityConfig(alpha_w=args.alpha_w, alpha_c=args.alpha_c,
                          alpha_d=args.alpha_d)
    ocfg = AdamWConfig(lr=args.lr)
    sched = lambda step: linear_warmup_cosine(step, 10, args.steps)

    key = jax.random.PRNGKey(args.seed)
    params, opt_state = init_train_state(key, cfg)
    step0 = 0

    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every)
        restored, meta = mgr.restore_or_none((params, opt_state))
        if restored is not None:
            params, opt_state = restored
            step0 = int(meta["step"]) + 1
            print(f"resumed from step {meta['step']}")

    update = jax.jit(build_update_step(cfg, ocfg, scfg, sched),
                     donate_argnums=(0, 1))

    losses = []
    t_train0 = time.time()
    for step in range(step0, args.steps):
        kstep = jax.random.fold_in(key, step)
        # SMD: data-level sparsity — skip the whole iteration w.p. α_D
        if scfg.alpha_d > 0 and not bool(smd_keep_iteration(kstep, scfg)):
            continue
        batch_np = lm_batch(args.seed, step, args.batch, args.seq, cfg.vocab)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        t0 = time.time()
        params, opt_state, loss, gnorm = update(params, opt_state, batch,
                                                kstep)
        loss = float(loss)
        dt = (time.time() - t0) * 1e3
        if args.deadline_ms and dt > args.deadline_ms:
            print(f"step {step}: DEADLINE exceeded ({dt:.0f}ms "
                  f"> {args.deadline_ms}ms) — straggler logged")
        losses.append(loss)
        if step % args.log_every == 0:
            print(f"step {step}: loss={loss:.4f} gnorm={float(gnorm):.3f} "
                  f"({dt:.0f}ms)", flush=True)
        if mgr is not None:
            saved = mgr.maybe_save(step, (params, opt_state),
                                   {"loss": loss})
            if mgr.preempted:
                print(f"SIGTERM: checkpointed at step {step}, exiting")
                return 0
    print(f"done: first-10 mean loss {np.mean(losses[:10]):.4f} → "
          f"last-10 mean {np.mean(losses[-10:]):.4f} "
          f"({time.time()-t_train0:.0f}s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
