"""Fault-tolerant training loop: checkpoint/restart, failure injection,
straggler watchdog, elastic re-mesh on restore.

The loop is deliberately structured the way a 1000-node fleet driver would
be:

* every step runs under a deadline watchdog — a straggling step (here:
  simulated) is logged and counted; on a real fleet the same hook triggers
  re-dispatch of the slow host's shard;
* any exception inside a step (injected in tests via ``failure_hook``)
  rolls back to the latest checkpoint and resumes — the data pipeline step
  counter restores from the checkpoint's extra dict so the batch sequence is
  bit-identical.  The step program is compiled once, before that retry
  loop: a program the compiler refuses is raised at once, since restoring a
  checkpoint cannot fix it;
* restore goes through NamedShardings of the *current* mesh, so a run can
  resume on a different device count (elastic re-mesh) — exercised in
  tests/test_train_loop.py with different host-device meshes.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from repro.checkpoint.ckpt import CheckpointManager
from repro.configs.base import ModelConfig, RunConfig
from repro.data.pipeline import SyntheticPipeline, device_batch
from repro.distributed import sharding as shd
from repro.models import model_zoo
from repro.obs import instrument as obs
from repro.train import step as train_step_mod

log = logging.getLogger("repro.train")


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    ckpt_every: int = 20
    ckpt_dir: str = ""            # required: the loop restores what it finds
    keep: int = 3
    step_deadline_s: float = 120.0
    max_restarts: int = 3


def _state_shardings(api, rc, mesh, abstract):
    logical = train_step_mod.state_logical_specs(api, rc, mesh)
    specs = train_step_mod.resolve_state_specs(logical, abstract)
    if mesh is None:
        return None
    return jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), specs)


def train(cfg: ModelConfig, rc: RunConfig, loop: LoopConfig,
          mesh=None, failure_hook: Optional[Callable[[int], None]] = None,
          log_every: int = 10) -> Dict[str, list]:
    """Run the loop; returns metric history."""
    if not loop.ckpt_dir:
        raise ValueError("LoopConfig.ckpt_dir must name the run's directory")
    rules = shd.Rules(mesh=mesh, seq_shard=rc.seq_shard, fsdp=rc.fsdp,
                      shard_vocab=rc.shard_vocab)
    with shd.use_rules(rules):
        api = model_zoo.get_api(cfg, rc)
        mgr = CheckpointManager(loop.ckpt_dir, keep=loop.keep)
        pipeline = SyntheticPipeline(cfg, rc)
        step_fn = train_step_mod.make_train_step(api, cfg, rc, mesh)
        abstract = train_step_mod.abstract_state(api, rc, mesh)
        shardings = _state_shardings(api, rc, mesh, abstract)
        jit_step = jax.jit(step_fn,
                           in_shardings=(shardings, None) if shardings else None,
                           out_shardings=(shardings, None) if shardings else None,
                           donate_argnums=(0,))

        def fresh_state():
            return train_step_mod.init_state(
                api, rc, jax.random.PRNGKey(0), mesh)

        def restore_latest():
            step_num = mgr.latest_step()
            if step_num is None:
                return fresh_state()
            flat_sh = jax.tree.leaves(shardings) if shardings else None
            state, extra = mgr.restore(
                step_num, abstract,
                sharding_fn=(lambda i, ref: flat_sh[i]) if flat_sh else None)
            pipeline.restore(extra)
            log.info("restored checkpoint at step %d", step_num)
            return state

        state = restore_latest()
        step_exec = jit_step.lower(
            state, model_zoo.input_specs(cfg, rc)).compile()
        history: Dict[str, list] = {"loss": [], "step_time": [], "stragglers": 0,
                                    "restarts": 0}
        restarts = 0
        while int(jax.device_get(state.step)) < loop.total_steps:
            step_num = int(jax.device_get(state.step))
            try:
                if failure_hook is not None:
                    failure_hook(step_num)
                batch_np = pipeline.next()
                batch = device_batch(batch_np, cfg, rc)
                t0 = time.monotonic()
                # span wraps the traced call from outside (obs records
                # nothing inside jit-compiled code — see repro.obs)
                with obs.span("train/step", step=step_num, arch=cfg.name):
                    state, metrics = step_exec(state, batch)
                    loss = float(jax.device_get(metrics["loss"]))
                dt = time.monotonic() - t0
                obs.gauge_set("train/loss", loss, arch=cfg.name)
                obs.counter_inc("train/steps", 1, arch=cfg.name)
                obs.counter_inc("train/tokens",
                                int(np.prod(batch_np["tokens"].shape))
                                if "tokens" in batch_np else 0, arch=cfg.name)
                if dt > loop.step_deadline_s:
                    history["stragglers"] += 1
                    obs.counter_inc("train/stragglers", 1, arch=cfg.name)
                    log.warning("step %d exceeded deadline (%.1fs) — "
                                "straggler mitigation would re-dispatch",
                                step_num, dt)
                history["loss"].append(loss)
                history["step_time"].append(dt)
                if not np.isfinite(loss):
                    raise FloatingPointError(f"non-finite loss at {step_num}")
                if log_every and step_num % log_every == 0:
                    log.info("step %d loss %.4f (%.2fs)", step_num, loss, dt)
                if (step_num + 1) % loop.ckpt_every == 0:
                    mgr.save(step_num + 1, state, extra=pipeline.state())
            except (FloatingPointError, RuntimeError, ValueError) as e:
                restarts += 1
                history["restarts"] = restarts
                log.error("step %d failed (%s); restart %d/%d", step_num, e,
                          restarts, loop.max_restarts)
                if restarts > loop.max_restarts:
                    raise
                state = restore_latest()
        mgr.save(int(jax.device_get(state.step)), state,
                 extra=pipeline.state())
        mgr.wait()
        return history
