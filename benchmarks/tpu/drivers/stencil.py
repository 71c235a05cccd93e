"""Stencil driver: repeated passes of ``ops.jacobi1d_tiled`` over one field.

One unit is one pass of ``t_steps`` Jacobi steps over the whole field; each
pass's output is the next pass's input, as a time-stepping code runs.  The
field is made on the device from the seed (standard normal f32).  Set-up
runs the first pass, which compiles; the reference covers it too.  Passes
are sent ahead: a unit waits for the pass ``ahead`` passes before its own,
so that a stall of the host, some seconds at most, leaves the chip fed.

Correctness: once the window has closed, the field is made again from the
seed and advanced by the plain reference (``reference/jacobi.py``) through
as many passes as the program ran; every cell, both boundaries included,
is compared.  ``control`` puts the reference, computed in bfloat16, in the
program's place.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp

import counts
import weights
from reference import jacobi as ref
from repro.kernels import ops


def make_field(seed: int, n: int):
    return jax.jit(lambda k: jax.random.normal(k, (n,), jnp.float32))(
        weights.seed_key(seed))


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 control: bool = False):
        self.c, self.t, self.seed, self.control = config, traffic, seed, control
        self.n = int(config["cells"])
        self.T = int(traffic["t_steps"])
        self.passes = 0
        self.sent = collections.deque()     # passes sent, not yet waited for
        self.attempted = self.failed = 0

    def _pass(self, x):
        if self.control:
            return ref.one_pass(x, self.T, jnp.bfloat16)
        return ops.jacobi1d_tiled(x, self.T, width=int(self.t["width"]),
                                  use_pallas=self.t["use_pallas"])

    def setup(self) -> None:
        self.x = self._pass(make_field(self.seed, self.n))
        self.x.block_until_ready()
        self.passes = 1

    def unit(self) -> None:
        with jax.profiler.TraceAnnotation("bench/pass"):
            self.x = self._pass(self.x)
            self.sent.append(self.x)
            if len(self.sent) > int(self.t["ahead"]):
                self.sent.popleft().block_until_ready()
        self.passes += 1
        self.attempted += 1

    def drain(self) -> None:
        while self.sent:
            self.sent.popleft().block_until_ready()

    def e2e(self, window_s: float) -> dict:
        return {"stencil_updates_per_s":
                self.attempted * self.n * self.T / window_s}

    def layer_counts(self, pk) -> dict:
        return {"passes": self.attempted,
                "pass_bytes": counts.jacobi_pass_bytes(self.n)}

    def check(self):
        got = self.x
        self.x = None
        want = ref.passes(make_field(self.seed, self.n), self.T, self.passes)
        err = float(jnp.max(jnp.abs(got - want)))
        finite = bool(jnp.all(jnp.isfinite(got)))
        return [("max_abs_error", err if finite else float("inf"),
                 self.t["limits"]["max_abs_error"])]
