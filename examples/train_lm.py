"""End-to-end training driver: data pipeline -> sharded train step ->
checkpointing -> fault-tolerant supervisor.

CPU demo (default):    PYTHONPATH=src python examples/train_lm.py
~100M model (TPU pod): PYTHONPATH=src python examples/train_lm.py \
                           --preset 100m --steps 300
Resume after crash:    re-run the same command — the supervisor restores
                       the latest atomic checkpoint automatically.
"""

import argparse
import dataclasses
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.checkpoint import Checkpointer
from repro.configs import ARCHS, reduced
from repro.configs.base import ArchConfig, ShapeCell
from repro.data import prefetch, synthetic_batches
from repro.launch.mesh import make_test_mesh
from repro.launch.train import build_train_step, init_train_state
from repro.runtime import run_with_recovery

PRESETS = {
    # ~2M params: CPU-friendly smoke run
    "tiny": dict(n_layers=4, d_model=128, n_heads=4, d_ff=512, vocab=2048),
    # ~25M params
    "small": dict(n_layers=8, d_model=384, n_heads=8, d_ff=1536, vocab=8192),
    # ~100M params: the end-to-end target (run on real accelerators)
    "100m": dict(n_layers=12, d_model=768, n_heads=12, d_ff=3072,
                 vocab=32000),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b",
                    choices=sorted(ARCHS))
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()

    p = PRESETS[args.preset]
    cfg = reduced(ARCHS[args.arch], **p)
    cfg = dataclasses.replace(cfg, remat=False)
    cell = ShapeCell("train", args.seq, args.batch, "train")
    mesh = make_test_mesh(data=1, model=1)

    with mesh:
        jfn, (aval, sspecs), _ = build_train_step(cfg, cell, mesh,
                                                  donate=False)
        ck = Checkpointer(args.ckpt_dir, keep=2, async_write=True)
        batches = prefetch(synthetic_batches(cfg, cell, seed=0), depth=2)

        def run_steps(start, end, state):
            it = prefetch(synthetic_batches(cfg, cell, seed=0,
                                            start_step=start), depth=2)
            for s in range(start, end):
                state, metrics = jfn(state, next(it))
                if (s + 1) % 5 == 0 or s == start:
                    print(f"step {s+1:4d}  loss {float(metrics['loss']):.4f}"
                          f"  gnorm {float(metrics.get('grad_norm', 0)):.3f}")
                if (s + 1) % args.ckpt_every == 0:
                    ck.save(s + 1, state)
            ck.wait()
            return state

        resume = ck.latest_step()
        if resume:
            print(f"resuming from checkpoint step {resume}")
            state = ck.restore(aval)
        else:
            state = init_train_state(cfg, mesh, jax.random.key(0))

        state, failures = run_with_recovery(
            steps=args.steps, run_steps=run_steps, checkpointer=ck,
            state0=state)
        print(f"done at step {int(state['step'])}; "
              f"{len(failures)} recovered failures")


if __name__ == "__main__":
    main()
