"""Logical-axis sharding rules (MaxText-style) for the whole framework.

Models annotate tensors with *logical* axis names; this module resolves them
to mesh axes for the active mesh and run config:

  batch     -> ('pod', 'data')   data parallelism (pod axis included if present)
  seq       -> 'model'           sequence/context parallelism for activations
  heads     -> 'model'           attention-head tensor parallelism
  ff        -> 'model'           MLP hidden tensor parallelism
  vocab     -> 'model'           embedding/unembedding vocab sharding
  cache_seq -> 'model'           decode KV-cache length sharding (flash-decode)
  fsdp      -> 'data'            ZeRO-3 style parameter/optimizer sharding
  experts   -> None              baseline: experts TP-sharded via 'ff' inside
                                  (an EP mesh variant is a §Perf experiment)

A rule only applies when the dimension size divides the mesh axis size
(whisper's 6 heads, hymba's 32001 vocab etc. fall back to replication —
uneven shardings would silently pad).

No global state is touched by importing this module; the launcher installs a
context via ``use_rules`` / ``set_rules``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Rules:
    mesh: Optional[Mesh] = None
    seq_shard: bool = True
    fsdp: bool = True
    shard_vocab: bool = True
    #: axes handled manually (e.g. 'pod' inside a shard_map body) — excluded
    #: from constraint resolution
    exclude: frozenset = frozenset()

    def axis_size(self, name: str) -> int:
        if self.mesh is None or name not in self.mesh.axis_names:
            return 1
        return self.mesh.shape[name]

    def resolve(self, logical: Optional[str], dim: int):
        """Logical name + dim size -> mesh axis (or None)."""
        if self.mesh is None or logical is None:
            return None
        names = tuple(a for a in self.mesh.axis_names if a not in self.exclude)
        if logical == "batch":
            axes = tuple(a for a in ("pod", "data") if a in names)
            total = int(np.prod([self.mesh.shape[a] for a in axes])) if axes else 1
            return axes if axes and dim % total == 0 else None
        if logical == "fsdp":
            if not self.fsdp:
                return None
            return "data" if "data" in names and dim % self.axis_size("data") == 0 else None
        model = ("model" if "model" in names
                 and dim % self.axis_size("model") == 0 else None)
        if logical == "seq":
            return model if self.seq_shard else None
        if logical == "vocab" and not self.shard_vocab:
            return None
        if logical in ("heads", "ff", "vocab", "cache_seq", "tp"):
            return model
        if logical == "experts":
            return None
        raise KeyError(f"unknown logical axis {logical!r}")

    def spec(self, shape: Sequence[int], logical: Sequence[Optional[str]]) -> P:
        assert len(shape) == len(logical), (shape, logical)
        return P(*(self.resolve(l, d) for l, d in zip(logical, shape)))


_local = threading.local()


def set_rules(rules: Optional[Rules]) -> None:
    _local.rules = rules


def get_rules() -> Optional[Rules]:
    return getattr(_local, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Rules):
    prev = get_rules()
    set_rules(rules)
    try:
        yield rules
    finally:
        set_rules(prev)


def act(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Constrain an activation to its logical sharding (no-op without mesh)."""
    r = get_rules()
    if r is None or r.mesh is None:
        return x
    spec = r.spec(x.shape, logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(r.mesh, spec))


def _is_logical_leaf(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def named_sharding(spec: P) -> Optional[NamedSharding]:
    r = get_rules()
    if r is None or r.mesh is None:
        return None
    return NamedSharding(r.mesh, spec)

