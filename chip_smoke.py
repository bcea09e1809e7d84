"""Bring the system up on a TPU and check what comes out.

    python chip_smoke.py              # one chip: serve, cnn and train
    python chip_smoke.py --chips 4    # only the sharded train step on a
                                      # (data=2, model=2) mesh, against the
                                      # same first step on one chip

Every phase goes through the entry points a user calls, with random
weights made from ``--seed``:

  serve  a SynergyServer on the default engine pool (xla, pallas,
         reference, neon-vpu; no retry policy) serves granite-3-2b at its
         published widths and full depth with bf16 weights.  Every request
         must get its tokens, one decode step on the server's path must
         match the same step pinned to the XLA engine, and one GEMM at the
         serving tile pinned to each engine of the pool must run on the
         chip and match the XLA engine.
  cnn    a batch of the paper's CIFAR_full frames through ``cnn_forward``
         split across the pool, against ``cnn_forward(engine="xla")``.
  train  ``build_train_step`` at granite widths, depth cut to 4 layers so
         fp32 parameters and AdamW fit one chip; every loss must be finite.

Earlier lines report seconds, tokens, frames, peak device bytes, per-engine
jobs and steals, and the compile cache directory.  The last line is one
JSON object, ``{"ok": true, "device": {...}}``.  Where JAX finds no TPU the
script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
import numpy as np                                          # noqa: E402

from repro.configs import ARCHS                             # noqa: E402
from repro.configs.base import ShapeCell                    # noqa: E402
from repro.configs.paper_cnns import CIFAR_FULL             # noqa: E402
from repro.core.serving import (_SERVE_TILE, Request,      # noqa: E402
                                SynergyServer)
from repro.core.synergy_mm import synergy_matmul            # noqa: E402
from repro.data.pipeline import make_batch                  # noqa: E402
from repro.engines import engine_scope                      # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_test_mesh                # noqa: E402
from repro.launch.train import build_train_step, init_train_state  # noqa
from repro.models import decode_step, init_model            # noqa: E402
from repro.models.cnn import cnn_forward, init_cnn          # noqa: E402
from repro.soc import SynergyRuntime                        # noqa: E402

#: granite-3-2b as served: all 40 layers at the published widths; bf16
#: weights (a config value) so the weights and the server's stacked fp32
#: copy of every FFN up-projection fit one chip's 16 GB together
SERVE_CFG = dataclasses.replace(ARCHS["granite-3-2b"], param_dtype="bfloat16")
#: granite widths, depth cut so fp32 params + AdamW (~5.5 GB) fit one chip
TRAIN_CFG = dataclasses.replace(ARCHS["granite-3-2b"], n_layers=4)
TRAIN_CELL = ShapeCell("smoke", seq_len=512, global_batch=8, kind="train")

#: Relative bounds, each ||got - ref|| / ||ref|| (Frobenius).  XLA's
#: default TPU matmul rounds f32 operands to bf16 in one pass (~2e-3
#: relative per GEMM), the Pallas and VPU engines accumulate in f32, and
#: the decode logits pass through 40 bf16 layers.
GEMM_RTOL = 1e-2
CNN_RTOL = 2e-2
LOGITS_RTOL = 5e-2
#: |loss(4 chips) - loss(1 chip)| / |loss(1 chip)|: the same bf16 step,
#: only the reduction order differs
LOSS_RTOL = 1e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def rel_err(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))


def on_chip(x: jax.Array) -> bool:
    return all(d.platform == "tpu" for d in x.devices())


def mem_stat(dev, key: str):
    """One of the device's memory counters (None where the backend keeps
    none)."""
    return (dev.memory_stats() or {}).get(key)


def engine_counts(rt: SynergyRuntime) -> dict:
    return {name: {"jobs": e["jobs"], "steals": e["steals"]}
            for name, e in rt.stats()["engines"].items()}


# --------------------------------------------------------------- phases
def serve(rt: SynergyRuntime, cfg, *, seed: int, n_req: int = 8,
          new_tokens: int = 16, prompt=(16, 64), slots: int = 8,
          max_len: int = 128) -> dict:
    print(f"serve: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} param_dtype={cfg.param_dtype} "
          f"compute_dtype={cfg.compute_dtype}", flush=True)
    params = jax.block_until_ready(
        jax.jit(lambda k: init_model(cfg, k))(jax.random.key(seed)))
    rng = np.random.default_rng(seed)

    def request(rid: int, n: int, new: int) -> Request:
        toks = rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
        return Request(rid, jnp.asarray(toks), max_new_tokens=new)

    srv = SynergyServer(cfg, params, slots=slots, max_len=max_len,
                        prefill_len=prompt[1], runtime=rt)
    # warm-up: one short request compiles the decode step and the panels
    t0 = time.perf_counter()
    srv.submit(request(-1, prompt[0], 2))
    srv.run()
    warm_s = time.perf_counter() - t0
    srv.reset_stats()

    reqs = [request(i, int(rng.integers(prompt[0], prompt[1] + 1)),
                    new_tokens) for i in range(n_req)]
    t0 = time.perf_counter()
    for r in reqs:
        srv.submit(r)
    stats = srv.run()
    run_s = time.perf_counter() - t0
    for r in reqs:
        check(len(r.out) == new_tokens,
              f"request {r.rid} got {len(r.out)} of {new_tokens} tokens")
        check(all(0 <= t < cfg.padded_vocab for t in r.out),
              f"request {r.rid} got a token outside the vocabulary")
    served = sum(len(r.out) for r in reqs)
    print(f"serve: {n_req} requests, prompts "
          f"{sorted(len(r.tokens) for r in reqs)}, {served} tokens served "
          f"({stats.tokens_out} decoded + {stats.prefills} from prefill); "
          f"warm-up (compiles) {warm_s:.2f} s, run {run_s:.2f} s", flush=True)

    # one decode step on the server's own path against the same step
    # traced with every GEMM pinned to the XLA engine
    toks = jnp.asarray([[r.out[-1]] for r in reqs[:slots]]
                       + [[0]] * (slots - min(slots, n_req)), jnp.int32)
    pos = jnp.asarray(np.minimum(srv.slot_pos, max_len - 1), jnp.int32)
    args = (params, srv.cache, toks, pos)
    check("tpu_custom_call" in srv._decode.lower(*args).as_text(),
          "the server's decode step runs no Pallas kernel")
    got, _ = srv._decode(*args)
    with engine_scope("xla"):
        pinned = jax.jit(lambda p, c, t, q: decode_step(cfg, p, c, t, q))
        ref, _ = pinned(*args)
    err = rel_err(got, ref)
    print(f"serve: decode logits vs XLA-pinned step: rel err {err:.3e} "
          f"(bound {LOGITS_RTOL})", flush=True)
    check(bool(jnp.all(jnp.isfinite(got))), "non-finite decode logits")
    check(err <= LOGITS_RTOL, f"decode logits rel err {err:.3e}")

    # one GEMM at the serving tile pinned to each engine of the pool, so
    # no engine goes untested because stealing happened to pass it by
    k1, k2 = jax.random.split(jax.random.key(seed + 1))
    a = jax.random.normal(k1, (slots, cfg.d_model), jnp.float32)
    w = (jax.random.normal(k2, (cfg.d_model, 2 * cfg.d_ff), jnp.float32)
         * cfg.d_model ** -0.5)
    ref = synergy_matmul(a, w, tile=_SERVE_TILE, engine="xla")
    pool = sorted(rt.stats()["engines"])
    for name in pool:
        y = synergy_matmul(a, w, tile=_SERVE_TILE, engine=name)
        err = rel_err(y, ref)
        print(f"serve: GEMM {a.shape}x{w.shape} tile {_SERVE_TILE} pinned to "
              f"{name}: on chip {on_chip(y)}, rel err vs xla {err:.3e}",
              flush=True)
        check(on_chip(y), f"engine {name} did not run on the chip")
        check(err <= GEMM_RTOL, f"engine {name} rel err {err:.3e}")
    srv.close(release_pool=False)
    return {"warm_s": warm_s, "run_s": run_s, "tokens": served}


def cnn(rt: SynergyRuntime, cnn_cfg, *, seed: int, frames: int = 64) -> dict:
    params = init_cnn(cnn_cfg, jax.random.key(seed))
    x = jax.random.normal(jax.random.key(seed + 2),
                          (frames, cnn_cfg.input_hw, cnn_cfg.input_hw,
                           cnn_cfg.cin), jnp.float32)
    ref = jax.block_until_ready(cnn_forward(cnn_cfg, params, x, engine="xla"))
    t0 = time.perf_counter()
    jax.block_until_ready(cnn_forward(cnn_cfg, params, x, runtime=rt))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    y = jax.block_until_ready(cnn_forward(cnn_cfg, params, x, runtime=rt))
    run_s = time.perf_counter() - t0
    ok = np.allclose(np.asarray(y), np.asarray(ref), rtol=CNN_RTOL,
                     atol=CNN_RTOL * float(jnp.max(jnp.abs(ref))))
    err = rel_err(y, ref)
    print(f"cnn: {cnn_cfg.name} {frames} frames through the pool: first call "
          f"(compiles) {first_s:.2f} s, run {run_s:.2f} s "
          f"({frames / run_s:.1f} frames/s); allclose vs xla "
          f"(rtol=atol/max|ref|={CNN_RTOL}) {ok}, rel err {err:.3e}",
          flush=True)
    check(y.shape == ref.shape and on_chip(y), "cnn output shape or device")
    check(bool(ok), f"cnn logits differ from xla (rel err {err:.3e})")
    return {"first_s": first_s, "run_s": run_s, "frames": frames}


def train_steps(cfg, cell, devices, *, seed: int, steps: int,
                data: int = 1, model: int = 1):
    """``steps`` train steps on a (data, model) mesh over ``devices``:
    returns the losses, the seconds of each step and the state's bytes
    on each device before the first step."""
    mesh = make_test_mesh(data, model, devices=devices)
    losses, secs = [], []
    with jax.set_mesh(mesh):
        jfn, _, _ = build_train_step(cfg, cell, mesh)
        state = init_train_state(cfg, mesh, jax.random.key(seed))
        leaves = jax.tree.leaves(jax.block_until_ready(state))
        held = {d.id: sum(s.data.nbytes for x in leaves
                          for s in x.addressable_shards if s.device == d)
                for d in devices}
        for step in range(steps):
            batch = make_batch(cfg, cell, seed, step)
            t0 = time.perf_counter()
            state, metrics = jfn(state, batch)
            losses.append(float(metrics["loss"]))
            secs.append(time.perf_counter() - t0)
    total = sum(x.nbytes for x in leaves)
    return losses, secs, held, total


def train(cfg, cell, dev, *, seed: int, steps: int = 3) -> dict:
    losses, secs, _, total = train_steps(cfg, cell, [dev], seed=seed,
                                         steps=steps)
    print(f"train: {cfg.name} widths, n_layers={cfg.n_layers} (cut from 40), "
          f"{total / 1e9:.2f} GB params+AdamW state, batch "
          f"{cell.global_batch}x{cell.seq_len}; losses {losses}; step 1 "
          f"(compiles) {secs[0]:.2f} s, later steps "
          f"{[round(s, 3) for s in secs[1:]]} s", flush=True)
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    return {"first_s": secs[0], "run_s": sum(secs[1:]), "losses": losses}


def sharded_train(cfg, cell, devs, *, seed: int) -> None:
    """The first train step on a (data=2, model=2) mesh over four chips
    against the same step on one chip, in this process."""
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    before = [mem_stat(d, "bytes_in_use") for d in devs[:4]]
    (l4,), (s4,), held, total = train_steps(cfg, cell, devs[:4], seed=seed,
                                            steps=1, data=2, model=2)
    print(f"train x4: mesh (data=2, model=2); state {total} bytes; bytes "
          f"held per device {held}; bytes in use per device before the "
          f"state {before}; loss {l4}; first step (compiles) {s4:.2f} s",
          flush=True)
    (l1,), (s1,), _, _ = train_steps(cfg, cell, devs[:1], seed=seed, steps=1)
    err = abs(l4 - l1) / abs(l1)
    print(f"train x1: loss {l1}; first step (compiles) {s1:.2f} s; "
          f"|loss x4 - loss x1| / loss x1 = {err:.3e} (bound {LOSS_RTOL})",
          flush=True)
    check(math.isfinite(l4) and math.isfinite(l1), "non-finite loss")
    check(err <= LOSS_RTOL, f"4-chip loss {l4} vs 1-chip {l1}")
    check(all(0 < held[d.id] <= 0.75 * total for d in devs[:4]),
          f"parameters not spread over 4 devices: {held}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX sees "
                         f"{dev.platform}); nothing was run")
    cache = enable_compile_cache(ROOT)
    print(f"device: {dev.device_kind} x{len(devs)}; compile cache {cache}",
          flush=True)

    if args.chips == 4:
        sharded_train(TRAIN_CFG, TRAIN_CELL, devs, seed=args.seed)
    else:
        rt = SynergyRuntime()
        t = {"serve": serve(rt, SERVE_CFG, seed=args.seed)}
        print(f"serve: peak device bytes "
              f"{mem_stat(dev, 'peak_bytes_in_use')}", flush=True)
        t["cnn"] = cnn(rt, CIFAR_FULL, seed=args.seed)
        st = rt.stats()
        print(f"pool: engines {engine_counts(rt)}; total steals "
              f"{st['total_steals']}; retries {st['retries']}", flush=True)
        check(st["retries"] == 0, f"{st['retries']} runtime retries")
        rt.shutdown()
        t["train"] = train(TRAIN_CFG, TRAIN_CELL, dev, seed=args.seed)
        print(f"peak device bytes {mem_stat(dev, 'peak_bytes_in_use')}; "
              f"phases {t}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
