"""Training loop: jitted step builders + the orchestration layer.

Two step modes:

  * ``make_train_step`` — GSPMD auto mode: one jit with in/out shardings;
    the mesh partitions everything (TP/FSDP/EP per the model's specs).
    Microbatching = lax.scan gradient accumulation inside the step.
  * ``make_explicit_dp_step`` — shard_map over the batch axes with
    *replicated* params: the DP gradient sync is explicit, so it can run
    compressed (int8 / PowerSGD, repro.train.compression) — the wire-level
    trick the auto mode can't express.

``fit`` wires the rest: data iterator, async checkpointing, restore-retry
fault tolerance, heartbeat, straggler detection, ProHD drift monitoring of
activations (the paper's technique as a first-class training feature).
"""
from __future__ import annotations

import dataclasses
import time
from pathlib import Path
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.train import checkpoint as ckpt_mod
from repro.train import compression as comp_mod
from repro.train.fault_tolerance import (
    Heartbeat,
    StragglerDetector,
    run_with_recovery,
)
from repro.train.optimizer import Optimizer


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    microbatches: int = 1          # gradient-accumulation chunks per step
    log_every: int = 10
    ckpt_every: int = 50
    ckpt_dir: str | None = None
    max_failures: int = 3
    drift_every: int = 0           # 0 = off; else ProHD drift check cadence
    compression: str | None = None  # None | "int8" | "powersgd"
    powersgd_rank: int = 4


# ---------------------------------------------------------------------------
# Set-distance metrics (losses / drift signals) via the repro.hd front door
# ---------------------------------------------------------------------------


def make_set_distance_metric(
    variant: str = "chamfer",
    method: str = "exact",
    backend: str = "auto",
    config=None,
):
    """Build a jit-friendly ``metric(x, y) -> HDResult`` for training code.

    The training loop's auxiliary losses and drift hooks used to hard-wire
    one estimator each (``prohd(...)`` here, ``chamfer(...)`` there); this
    returns a front-door engine call instead, so the estimator, variant and
    backend are run-time configuration.  Chamfer is the smooth choice for a
    loss term; ``method="prohd"`` gives the certified drift signal (see
    repro.core.streaming for the stateful monitor).

    Differentiability caveat: only the pure-JAX backends ("tiled",
    "dense") have autodiff rules — the Pallas kernel defines no VJP, and
    ``backend="auto"`` picks it on TPU at ≥512 rows/side.  Pass
    ``backend="tiled"`` explicitly when the metric sits under ``jax.grad``.
    """
    from repro.hd import HDConfig, HDEngine

    engine = HDEngine(
        variant=variant, method=method, backend=backend,
        config=config if config is not None else HDConfig(),
    )

    def metric(x, y, *, key=None):
        return engine(x, y, key=key)

    return metric


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


def make_train_step(
    loss_fn: Callable[[Any, Any], tuple[jnp.ndarray, dict]],
    optimizer: Optimizer,
    *,
    microbatches: int = 1,
    donate: bool = True,
    jit: bool = True,
):
    """GSPMD-auto train step: (params, opt_state, batch) → (params, opt_state, metrics).

    ``jit=False`` returns the raw python callable — the dry-run wraps it in
    its own jax.jit with explicit in/out shardings.
    """

    def step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        else:
            # split the batch's leading dim into microbatches and accumulate
            def split(x):
                b = x.shape[0]
                return x.reshape(microbatches, b // microbatches, *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def acc_body(carry, mb):
                g_acc, loss_acc = carry
                (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(lambda a, b_: a + b_.astype(jnp.float32), g_acc, g)
                return (g_acc, loss_acc + loss), metrics

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), metrics = jax.lax.scan(acc_body, (g0, 0.0), micro)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
            metrics = jax.tree.map(lambda m: m[-1], metrics)

        new_params, new_opt = optimizer.update(grads, opt_state, params)
        gnorm = jnp.sqrt(
            sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
        )
        metrics = dict(metrics, loss=loss, grad_norm=gnorm)
        return new_params, new_opt, metrics

    if not jit:
        return step
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def make_explicit_dp_step(
    loss_fn,
    optimizer: Optimizer,
    mesh: jax.sharding.Mesh,
    *,
    batch_axes: tuple[str, ...] = ("data",),
    compression: str | None = None,
    powersgd_rank: int = 4,
):
    """Explicit data-parallel step with compressed gradient all-reduce.

    Params replicated, batch sharded over ``batch_axes``; each shard
    computes local grads, then the DP sync runs int8 / PowerSGD compressed
    (repro.train.compression).  State carries the compressor's error
    feedback.  Returns (step_fn, init_comp_state_fn).
    """

    def local_grads(params, mb):
        (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        return loss, metrics, g

    def step(params, opt_state, comp_state, batch):
        def shard_fn(params, opt_state, comp_state, batch):
            loss, metrics, g = local_grads(params, batch)
            if compression == "int8":
                g, new_err = comp_mod.compressed_psum_int8(g, comp_state, batch_axes)
                comp_state = new_err
            elif compression == "powersgd":
                g, comp_state = comp_mod.powersgd_round(g, comp_state, batch_axes)
            else:
                g = jax.tree.map(lambda x: jax.lax.pmean(x, batch_axes), g)
            loss = jax.lax.pmean(loss, batch_axes)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, batch_axes), metrics)
            new_params, new_opt = optimizer.update(g, opt_state, params)
            return new_params, new_opt, comp_state, dict(metrics, loss=loss)

        rep = jax.tree.map(lambda _: P(), params)
        rep_opt = jax.tree.map(lambda _: P(), opt_state)
        rep_comp = jax.tree.map(lambda _: P(), comp_state)
        batch_spec = jax.tree.map(lambda _: P(batch_axes), batch)
        fn = shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(rep, rep_opt, rep_comp, batch_spec),
            out_specs=(rep, rep_opt, rep_comp, P()),
            check_vma=False,
        )
        return fn(params, opt_state, comp_state, batch)

    def init_comp_state(params, key=None):
        if compression == "int8":
            return comp_mod.init_error_tree(params)
        if compression == "powersgd":
            if key is None:
                key = jax.random.PRNGKey(0)
            return comp_mod.init_powersgd(params, powersgd_rank, key)
        return {}

    return jax.jit(step, donate_argnums=(0, 1, 2)), init_comp_state


# ---------------------------------------------------------------------------
# Orchestration
# ---------------------------------------------------------------------------


def fit(
    *,
    params: Any,
    optimizer: Optimizer,
    loss_fn,
    data_iter_fn: Callable[[int], Iterator[Any]],
    cfg: TrainConfig,
    drift_hook: Callable[[Any, dict], None] | None = None,
    log_fn: Callable[[int, dict], None] | None = None,
    _fail_at: int | None = None,  # test hook: inject a failure at this step
) -> tuple[Any, Any, list[dict]]:
    """Run the full fault-tolerant loop.  Returns (params, opt_state, logs)."""
    opt_state = optimizer.init(params)
    step_fn = make_train_step(loss_fn, optimizer, microbatches=cfg.microbatches)
    hb = Heartbeat()
    straggler = StragglerDetector()
    logs: list[dict] = []
    ckpt = ckpt_mod.AsyncCheckpointer(cfg.ckpt_dir) if cfg.ckpt_dir else None

    state = {"params": params, "opt": opt_state}
    failed_once = {"armed": _fail_at is not None}

    def restore() -> int:
        nonlocal state
        if cfg.ckpt_dir and ckpt_mod.latest_step(cfg.ckpt_dir) is not None:
            tree, step = ckpt_mod.restore(cfg.ckpt_dir, state)
            state = tree
            return step + 1
        return 0

    def run(start: int) -> int:
        nonlocal state
        it = data_iter_fn(start)
        for step in range(start, cfg.steps):
            t0 = time.monotonic()
            batch = next(it)
            if failed_once["armed"] and step == _fail_at:
                failed_once["armed"] = False
                raise RuntimeError(f"injected failure at step {step}")
            p, o, metrics = step_fn(state["params"], state["opt"], batch)
            state = {"params": p, "opt": o}
            hb.beat()
            dt = time.monotonic() - t0
            is_straggler = straggler.observe(dt)
            if step % cfg.log_every == 0 or step == cfg.steps - 1:
                rec = {k: float(v) for k, v in metrics.items()}
                rec.update(step=step, dt=dt, straggler=is_straggler)
                logs.append(rec)
                if log_fn:
                    log_fn(step, rec)
            if ckpt and cfg.ckpt_every and step % cfg.ckpt_every == 0 and step > 0:
                ckpt.save(step, state)
            if drift_hook and cfg.drift_every and step % cfg.drift_every == 0:
                drift_hook(state["params"], {"step": step})
        if ckpt:
            ckpt.save(cfg.steps - 1, state)
            ckpt.wait()
        return cfg.steps

    run_with_recovery(run, restore, max_failures=cfg.max_failures)
    return state["params"], state["opt"], logs
