"""One deployment, built from its configuration file and the run's seed.

The graph comes from the configuration's fixed graph seed (it is the
deployment's data); the classifier weights come from the run's seed, made
on the device in one jitted call in the type they are served in (float32).
T_s is the configuration's rule applied by the benchmark's own reference:
the median first-step Eq. 8 distance over the whole graph. One float64
reference per graph and model serves both T_s and the check after the
window: its operator is built once and its series once, to T_max.

The program sees the graph as its `Graph` container wrapped in a store,
and the weights as its parameter tree; the reference sees the same arrays
through `sbm.SBMGraph` and NumPy copies of the weights.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import numpy as np

from . import sbm
from .reference import Reference


@dataclasses.dataclass
class Deployment:
    config: dict
    graph: sbm.SBMGraph
    weights: Dict[int, Tuple[np.ndarray, np.ndarray]]   # NumPy copies, per order
    params: dict                                        # program parameter tree
    t_s: float
    ref: Reference = None       # float64 reference without heads, t_s set
    store: object = None        # program GraphStore
    gnn: object = None          # program GNNConfig
    nai: object = None          # program NAIConfig

    @property
    def model(self) -> dict:
        return self.config["model"]

    def reference(self, precision: str = "float64") -> Reference:
        if precision == "float64":
            return self.ref.with_weights(self.weights)
        m = self.model
        return Reference(self.graph, self.weights, r=m["r"], t_min=m["t_min"],
                         t_max=m["t_max"], t_s=self.t_s, precision=precision)


def _key(seed: int):
    """A PRNG key from a seed of any size (31 bits at a time)."""
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    rest = seed >> 31
    while rest:
        key = jax.random.fold_in(key, rest & 0x7FFFFFFF)
        rest >>= 31
    return key


@functools.lru_cache(maxsize=None)
def _weight_fn(f: int, c: int, t_max: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        out = {}
        for l in range(1, t_max + 1):
            kw, kb = jax.random.split(jax.random.fold_in(key, l))
            out[l] = {"w0": jax.random.normal(kw, (f, c), jnp.float32)
                      / jnp.sqrt(jnp.float32(f)),
                      "b0": 0.1 * jax.random.normal(kb, (c,), jnp.float32)}
        return out
    return make


@functools.lru_cache(maxsize=2)
def _graph(spec: str) -> sbm.SBMGraph:
    import json
    return sbm.generate(**json.loads(spec))


def build(config: dict, seed: int) -> Deployment:
    """The deployment of `config` with weights from `seed`, handed to the
    program as its own objects."""
    import json

    import jax
    from repro.gnn.graph import Graph
    from repro.gnn.models import GNNConfig
    from repro.gnn.nai import NAIConfig
    from repro.gnn.store import as_store

    g = _graph(json.dumps(config["graph"], sort_keys=True))
    m = config["model"]
    f, c = g.features.shape[1], g.num_classes
    params = {"cls": _weight_fn(f, c, m["t_max"])(_key(seed))}
    jax.block_until_ready(params)
    weights = {l: (np.asarray(p["w0"]), np.asarray(p["b0"]))
               for l, p in params["cls"].items()}
    ref = _reference(json.dumps(config["graph"], sort_keys=True), m["r"],
                     m["t_min"], m["t_max"])
    dep = Deployment(config=config, graph=g, weights=weights, params=params,
                     t_s=ref.t_s, ref=ref)
    pg = Graph(n=g.n, src=g.src, dst=g.dst, features=g.features,
               labels=g.labels, num_classes=c, train_idx=g.train_idx,
               unlabeled_idx=g.unlabeled_idx, test_idx=g.test_idx,
               name=config["name"])
    dep.store = as_store(pg)
    dep.gnn = GNNConfig("sgc", f, c, k=m["t_max"], r=m["r"], mlp_layers=1)
    dep.nai = NAIConfig(t_s=dep.t_s, t_min=m["t_min"], t_max=m["t_max"],
                        batch_size=config["engine"]["batch_size"])
    return dep


@functools.lru_cache(maxsize=2)
def _reference(spec: str, r: float, t_min: int, t_max: int) -> Reference:
    """The float64 reference of a graph and model, with its T_s."""
    ref = Reference(_graph(spec), {}, r=r, t_min=t_min, t_max=t_max)
    ref.t_s = ref.first_step_median()
    return ref
