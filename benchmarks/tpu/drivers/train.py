"""Train driver: the jitted step of ``train/step.make_train_step``, fed as
``train/loop.py`` feeds it (``SyntheticPipeline.next`` -> ``device_batch``
-> step -> ``float(loss)``), with no checkpoint in the window.

Set-up builds one object, the compiled step with its state (the
benchmark's weights from the seed, zero AdamW moments), and drives it
through its first three steps by the window's own call and feed; the
window goes on from there with the same object.  From those steps it keeps
each step's loss, the first gradient as AdamW got it (its first moment
after one step over ``1 - b1``), and each leaf's change after two updates
(the parameters that step three is handed, less the initial ones).

Correctness: once the window has closed and the program's state is gone,
the plain f32 reference (``reference/train.py``) runs the same two
updates on the same batches.  Compared, by the worst leaf: the gap between
the program's and the reference's norm of the first gradient, and of the
change, each over the larger of the reference's norm of that leaf and of
the median leaf.  Leaves whose reference gradient is under a thousandth of
the median leaf's move under AdamW by rounding alone and are left out of
the change.  Also compared: the largest gap of the three losses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import counts
import weights
from drivers import dense
from reference import train as ref
from repro.configs import base
from repro.data.pipeline import SyntheticPipeline, device_batch
from repro.models import model_zoo
from repro.optim import adamw
from repro.train import step as ts

FIRST_STEPS = 3


@jax.jit
def _leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def _diff_norms(a, b):
    return {k: jnp.sqrt(jnp.sum(jnp.square(a[k].astype(jnp.float32)
                                           - b[k].astype(jnp.float32))))
            for k in a}


def _host(tree) -> dict:
    return {k: float(v) for k, v in tree.items()}


def worst_leaf(prog: dict, want: dict, keys=None) -> float:
    """max over leaves of |prog - want| / max(want, median of want)."""
    keys = sorted(keys if keys is not None else want)
    med = float(np.median([want[k] for k in want]))
    return max(abs(prog[k] - want[k]) / max(want[k], med) for k in keys)


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 param_dtype: str = ""):
        self.c, self.t, self.seed = config, traffic, seed
        self.opt = config["training"]
        self.param_dtype = param_dtype or self.opt["param_dtype"]
        self.batches = []               # host batches of the first steps
        self.losses = []
        self.steps = 0
        self.attempted = self.failed = 0

    def _run_config(self) -> base.RunConfig:
        o, t = self.opt, self.t
        return base.RunConfig(
            seq_len=int(t["seq_len"]), global_batch=int(t["batch"]),
            kind="train", param_dtype=self.param_dtype,
            opt_dtype=o["opt_dtype"], remat=True, remat_policy="full",
            lr=o["lr"], weight_decay=o["weight_decay"],
            grad_clip=o["grad_clip"])

    def setup(self) -> None:
        self.cfg = dense.model_config(self.c)
        self.rc = rc = self._run_config()
        acfg = ts.adam_config(rc)
        for k in ("b1", "b2", "eps", "warmup_steps", "total_steps"):
            if getattr(acfg, k) != self.opt[k]:
                raise ValueError(f"the program's AdamW {k} is {getattr(acfg, k)}"
                                 f", the configuration states {self.opt[k]}")
        api = model_zoo.get_api(self.cfg, rc)
        step = ts.make_train_step(api, self.cfg, rc, None)
        params = dense.to_program(weights.make(self.seed, self.c, rc.jdtype))
        state = ts.TrainState(params=params, opt=adamw.init(params, acfg),
                              resid=None, step=jnp.zeros((), jnp.int32))
        self.compiled = jax.jit(step, donate_argnums=(0,)).lower(
            state, model_zoo.input_specs(self.cfg, rc)).compile()
        self.state = state
        self.pipe = SyntheticPipeline(self.cfg, rc, seed=self.seed)
        for i in range(FIRST_STEPS):
            if i == 2:
                p0 = weights.make(self.seed, self.c, rc.jdtype)
                self.change = _host(_diff_norms(
                    dense.from_program(self.state.params), p0))
                del p0
            self._step(keep=True)
            if i == 0:
                g = _leaf_norms(dense.from_program(self.state.opt.mu))
                self.first_grad = {k: v / (1 - acfg.b1)
                                   for k, v in _host(g).items()}

    def _step(self, keep: bool = False) -> None:
        with jax.profiler.TraceAnnotation("bench/batch"):
            host = self.pipe.next()
            batch = device_batch(host, self.cfg, self.rc)
        with jax.profiler.TraceAnnotation("bench/step"):
            self.state, metrics = self.compiled(self.state, batch)
            loss = float(metrics["loss"])
        if keep:
            self.batches.append(host)
            self.losses.append(loss)
        elif not np.isfinite(loss):
            self.failed += 1
        self.steps += 1

    def unit(self) -> None:
        self._step()
        self.attempted += 1

    def tokens_per_step(self) -> int:
        return int(self.t["batch"]) * int(self.t["seq_len"])

    def e2e(self, window_s: float) -> dict:
        return {"train_tokens_per_s":
                self.attempted * self.tokens_per_step() / window_s}

    def layer_counts(self, pk) -> dict:
        B, S = int(self.t["batch"]), int(self.t["seq_len"])
        _, _, H, KV, hd, L, _ = counts.dims(self.c)
        size = jnp.dtype(self.rc.jdtype).itemsize    # q, k, v as computed
        kernels = {"fwd": counts.flash_fwd(B, S, H, KV, hd, size),
                   "dkv": counts.flash_bwd_dkv(B, S, H, KV, hd, size),
                   "dq": counts.flash_bwd_dq(B, S, H, KV, hd, size)}
        return {"steps": self.attempted, "layers": L,
                "step_flops": counts.train_step_flops(self.c, B, S),
                "flash": {k: counts.least_seconds(f, b, pk)
                          for k, (f, b) in kernels.items()}}

    def hlo_text(self) -> str:
        """The compiled step's module, as the window ran it."""
        return self.compiled.as_text()

    def check(self):
        self.state = self.compiled = None          # the program's state goes
        w0 = weights.make(self.seed, self.c, jnp.float32)
        losses, g1, w2 = ref.two_steps(w0, self.batches, self.c, self.opt)
        grad = {k: float(np.linalg.norm(v)) for k, v in g1.items()}
        del g1
        change = _host(_diff_norms(w2, weights.make(self.seed, self.c,
                                                    jnp.float32)))
        med = float(np.median(list(grad.values())))
        moved = [k for k in grad if grad[k] >= 1e-3 * med]
        lim = self.t["limits"]
        loss_gap = max(abs(a - b) for a, b in zip(self.losses, losses))
        return [("loss_gap", loss_gap, lim["loss_gap"]),
                ("first_grad_gap", worst_leaf(self.first_grad, grad),
                 lim["first_grad_gap"]),
                ("change_gap", worst_leaf(self.change, change, moved),
                 lim["change_gap"]),
                ("failed_steps", self.failed, 0)]
