"""Training driver: jitted train step with full sharding, checkpointing,
fault tolerance hooks, and the Synergy between-step rebalancer.

``build_train_step`` returns the pjit-compiled step; ``train_loop`` is the
end-to-end driver used by examples/train_lm.py.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeCell
from repro.models import init_model, input_specs, loss_fn
from repro.optim import (AdamWConfig, AdafactorConfig, adamw_init,
                         adamw_update, adafactor_init, adafactor_update)
from .sharding import input_pspecs, state_pspecs, to_shardings

__all__ = ["make_train_state", "init_train_state", "build_train_step",
           "train_loop", "train_state_specs"]


def make_train_state(cfg: ArchConfig, key, opt_cfg=None) -> dict:
    params = init_model(cfg, key)
    if cfg.optimizer == "adafactor":
        opt = adafactor_init(params)
    else:
        opt = adamw_init(params)
    return {"params": params, "opt": opt, "step": jnp.zeros((), jnp.int32)}


def train_state_specs(cfg: ArchConfig, mesh):
    aval = jax.eval_shape(lambda: make_train_state(cfg, jax.random.key(0)))
    return aval, state_pspecs(cfg, aval, mesh)


def init_train_state(cfg: ArchConfig, mesh, key) -> dict:
    """The train state built by one program directly in its shardings on
    ``mesh``: no device ever holds the whole state on the way."""
    _, sspecs = train_state_specs(cfg, mesh)
    return jax.jit(lambda k: make_train_state(cfg, k),
                   out_shardings=to_shardings(sspecs, mesh))(key)


def _train_step(cfg: ArchConfig, opt_cfg, state: dict, batch: dict):
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch))(state["params"])
    if cfg.optimizer == "adafactor":
        new_params, new_opt, metrics = adafactor_update(
            opt_cfg, grads, state["opt"], state["params"])
    else:
        new_params, new_opt, metrics = adamw_update(
            opt_cfg, grads, state["opt"], state["params"])
    new_state = {"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}
    return new_state, {"loss": loss, **metrics}


def default_opt_cfg(cfg: ArchConfig):
    return (AdafactorConfig() if cfg.optimizer == "adafactor"
            else AdamWConfig())


def build_train_step(cfg: ArchConfig, cell: ShapeCell, mesh, *,
                     opt_cfg=None, donate: bool = True):
    """Returns (jitted_fn, state_specs, batch_specs) — ready to lower
    against ShapeDtypeStructs (dry-run) or run with real arrays."""
    opt_cfg = opt_cfg or default_opt_cfg(cfg)
    aval, sspecs = train_state_specs(cfg, mesh)
    in_specs = input_specs(cfg, cell)
    bspecs = input_pspecs(cfg, cell, in_specs, mesh)
    fn = functools.partial(_train_step, cfg, opt_cfg)
    jfn = jax.jit(
        fn,
        in_shardings=(to_shardings(sspecs, mesh),
                      to_shardings(bspecs, mesh)),
        out_shardings=(to_shardings(sspecs, mesh), None),
        donate_argnums=(0,) if donate else ())
    return jfn, (aval, sspecs), (in_specs, bspecs)


def train_loop(cfg: ArchConfig, mesh, *, steps: int, batch_iter,
               cell: ShapeCell, key=None, state=None, opt_cfg=None,
               checkpointer=None, ckpt_every: int = 0,
               on_step: Callable | None = None):
    """End-to-end loop: init (or resume), step, checkpoint, report."""
    key = key if key is not None else jax.random.key(0)
    jfn, (aval, sspecs), _ = build_train_step(cfg, cell, mesh,
                                              opt_cfg=opt_cfg)
    if state is None:
        state = init_train_state(cfg, mesh, key)
    history = []
    for _ in range(steps):
        batch = next(batch_iter)
        t0 = time.perf_counter()
        state, metrics = jfn(state, batch)
        metrics = {k: float(v) for k, v in metrics.items()}
        metrics["step_time_s"] = time.perf_counter() - t0
        history.append(metrics)
        step = int(state["step"])
        if checkpointer is not None and ckpt_every and step % ckpt_every == 0:
            checkpointer.save(step, state)
        if on_step is not None:
            on_step(step, metrics)
    return state, history
