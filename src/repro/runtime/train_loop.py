"""Training loop: steps + checkpoints + straggler monitor + exact resume."""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax

from repro.checkpointing.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.configs.base import ArchConfig
from repro.data.pipeline import DataConfig, MarkovTask
from repro.models.registry import Model
from repro.optim import AdamWConfig, init_state
from repro.optim.compression import ef_compress, init_error_state
from repro.runtime.fault_tolerance import StragglerMonitor
from repro.runtime.steps import (
    batch_shardings,
    make_train_step,
    state_shardings,
)


@dataclasses.dataclass
class TrainReport:
    steps: int
    losses: Dict[int, float]
    resumed_from: Optional[int]
    straggler_steps: int
    wall_s: float
    state: Any = None   # the final train state, in its (sharded) layout


def train(model: Model, *, steps: int, data_cfg: DataConfig,
          opt: Optional[AdamWConfig] = None, accum: int = 1,
          compress_grads: bool = False, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, log_every: int = 10, seed: int = 0,
          fail_at_step: Optional[int] = None) -> TrainReport:
    """Run `steps` optimizer steps; resumes exactly from `ckpt_dir` if present.

    `fail_at_step` injects a crash (fault-tolerance tests / demos).
    """
    t_start = time.time()
    opt = opt or AdamWConfig(total_steps=steps)
    task = MarkovTask(data_cfg)

    if compress_grads:
        # compress gradients with error feedback before the update
        def step_fn(state, batch):
            err = state.pop("grad_error")

            def loss_fn(params, b):
                loss, m = model.loss(params, b)
                return loss, m

            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state["params"], batch)
            grads, err = ef_compress(grads, err)
            from repro.optim import apply_updates
            new_state, om = apply_updates(state, grads, opt)
            new_state["grad_error"] = err
            return new_state, {"loss": loss, **om}
    else:
        step_fn = make_train_step(model, opt, accum=accum)

    # under a mesh, parameters and optimizer state are created, stepped and
    # kept in their sharded layout (`model.ctx` rules); without one, every
    # sharding is None and jit places everything on the default device
    shardings = state_shardings(model)
    if shardings is not None and compress_grads:
        shardings["grad_error"] = shardings["params"]
    jit_step = jax.jit(step_fn, in_shardings=(shardings, None),
                       out_shardings=(shardings, None), donate_argnums=(0,))

    def init(rng):
        params = model.init(rng)
        state = init_state(params)
        if compress_grads:
            state["grad_error"] = init_error_state(params)
        return state

    # ---- init or resume
    resumed_from = None
    state = jax.jit(init, out_shardings=shardings)(jax.random.PRNGKey(seed))
    if ckpt_dir is not None and latest_step(ckpt_dir) is not None:
        resumed_from = latest_step(ckpt_dir)
        state = restore(ckpt_dir, state)
        state = jax.device_put(state, shardings)

    ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir is not None else None
    monitor = StragglerMonitor()
    losses: Dict[int, float] = {}

    start = int(state["step"])
    for step in range(start, steps):
        if fail_at_step is not None and step == fail_at_step:
            raise RuntimeError(f"injected failure at step {step}")
        raw = task.batch_for_step(step)
        batch = jax.device_put(raw, batch_shardings(model.ctx, raw))
        with monitor.timed():
            state, metrics = jit_step(state, batch)
        if step % log_every == 0 or step == steps - 1:
            losses[step] = float(metrics["loss"])
        if ckpt is not None and (step + 1) % ckpt_every == 0:
            ckpt.save_async(state, step + 1)
    if ckpt is not None:
        ckpt.save_async(state, steps)
        ckpt.wait()
    return TrainReport(steps=steps, losses=losses, resumed_from=resumed_from,
                       straggler_steps=len(monitor.flagged),
                       wall_s=time.time() - t_start, state=state)
