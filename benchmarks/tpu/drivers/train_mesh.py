"""Train driver on the chips of one host: ``drivers/train.py``'s jitted
step, feed, window and check, with the train state sharded over a mesh.

The traffic file names the mesh's axes (``mesh``: data and model), the
batch, and the training settings (``training``, which the one-chip cell
keeps in its configuration file).  The program places everything as its
own rules say (``distributed/sharding.Rules`` with fsdp): parameters and
AdamW moments split over both axes, the batch over ``data``.  The
benchmark's weights are made from the seed straight into the state's
shardings (``weights.make``'s values; no chip holds a whole f32 copy).

Correctness: as ``drivers/train.py``, against ``reference/train_mesh.py``,
the plain f32 reference's two updates laid out over the same chips.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import weights
from drivers import dense, train
from reference import train_mesh as ref
from repro.data.pipeline import SyntheticPipeline, device_batch
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models import model_zoo
from repro.optim import adamw
from repro.train import step as ts


class Driver(train.Driver):
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 param_dtype: str = ""):
        super().__init__(dict(config, training=traffic["training"]), traffic,
                         seed, devices, param_dtype)
        self.devices = list(devices)

    def _weights(self, dtype, shardings: dict) -> dict:
        """``weights.make(seed, config, dtype)``, made in ``shardings``."""
        items = tuple(sorted((k, v) for k, v in self.c.items()
                             if isinstance(v, (int, float, str, bool))))
        make = jax.jit(lambda key: weights._make(key, items,
                                                 jnp.dtype(dtype).name),
                       out_shardings=shardings)
        return make(weights.seed_key(self.seed))

    def setup(self) -> None:
        axes = self.t["mesh"]
        self.mesh = make_mesh(tuple(axes.values()), tuple(axes),
                              devices=self.devices)
        self.cfg = dense.model_config(self.c)
        self.rc = rc = self._run_config()
        acfg = ts.adam_config(rc)
        for k in ("b1", "b2", "eps", "warmup_steps", "total_steps"):
            if getattr(acfg, k) != self.opt[k]:
                raise ValueError(f"the program's AdamW {k} is {getattr(acfg, k)}"
                                 f", the traffic states {self.opt[k]}")
        rules = shd.Rules(mesh=self.mesh, seq_shard=rc.seq_shard,
                          fsdp=rc.fsdp, shard_vocab=rc.shard_vocab)
        with shd.use_rules(rules):
            api = model_zoo.get_api(self.cfg, rc)
            step = ts.make_train_step(api, self.cfg, rc, self.mesh)
            abstract = ts.abstract_state(api, rc, self.mesh)
            specs = ts.resolve_state_specs(
                ts.state_logical_specs(api, rc, self.mesh), abstract)
            sh = jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)
            self.param_sh = dense.from_program(sh.params)
            rows = NamedSharding(self.mesh, P("data"))
            self.batch_sh = {k: rows for k in ("tokens", "labels")}
            params = dense.to_program(self._weights(rc.jdtype, self.param_sh))
            self.state = jax.jit(
                lambda p: ts.TrainState(params=p, opt=adamw.init(p, acfg),
                                        resid=None,
                                        step=jnp.zeros((), jnp.int32)),
                out_shardings=sh, donate_argnums=(0,))(params)
            batch = {k: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                             sharding=self.batch_sh[k])
                     for k, s in model_zoo.input_specs(self.cfg, rc).items()}
            self.compiled = jax.jit(
                step, in_shardings=(sh, self.batch_sh),
                out_shardings=(sh, None), donate_argnums=(0,)
            ).lower(self.state, batch).compile()
        self.pipe = SyntheticPipeline(self.cfg, rc, seed=self.seed)
        for i in range(train.FIRST_STEPS):
            if i == 2:
                p0 = self._weights(rc.jdtype, self.param_sh)
                self.change = train._host(train._diff_norms(
                    dense.from_program(self.state.params), p0))
                del p0
            self._step(keep=True)
            if i == 0:
                g = train._leaf_norms(dense.from_program(self.state.opt.mu))
                self.first_grad = {k: v / (1 - acfg.b1)
                                   for k, v in train._host(g).items()}

    def _step(self, keep: bool = False) -> None:
        with jax.profiler.TraceAnnotation("bench/batch"):
            host = self.pipe.next()
            batch = device_batch(host, self.cfg, self.rc, self.batch_sh)
        with jax.profiler.TraceAnnotation("bench/step"):
            self.state, metrics = self.compiled(self.state, batch)
            loss = float(metrics["loss"])
        if keep:
            self.batches.append(host)
            self.losses.append(loss)
        elif not np.isfinite(loss):
            self.failed += 1
        self.steps += 1

    def check(self):
        self.state = self.compiled = None          # the program's state goes
        mesh = ref.make_mesh(self.devices)
        rsh = ref.shardings(weights.shapes(self.c), mesh)
        losses, g1, w2 = ref.two_steps(self._weights(jnp.float32, rsh),
                                       self.batches, self.c, self.opt, mesh)
        grad = train._host(train._leaf_norms(g1))
        del g1
        change = train._host(train._diff_norms(
            w2, self._weights(jnp.float32, rsh)))
        med = float(np.median(list(grad.values())))
        moved = [k for k in grad if grad[k] >= 1e-3 * med]
        lim = self.t["limits"]
        loss_gap = max(abs(a - b) for a, b in zip(self.losses, losses))
        return [("loss_gap", loss_gap, lim["loss_gap"]),
                ("first_grad_gap", train.worst_leaf(self.first_grad, grad),
                 lim["first_grad_gap"]),
                ("change_gap", train.worst_leaf(self.change, change, moved),
                 lim["change_gap"]),
                ("failed_steps", self.failed, 0)]
