"""Linear-propagation scalable GNNs (paper §2.2) as per-order classifiers.

NAI needs one classifier f^(l) per propagation order l = 1..k. The base
model decides what f^(l) consumes:
    SGC   : X^(l)                      (linear/MLP head)
    S2GC  : mean(X^(0)..X^(l))
    SIGN  : concat(X^(0)..X^(l)) -> MLP
    GAMLP : node-wise attention over X^(0)..X^(l) -> MLP  (JK-attention form)
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.nn.params import ParamDef, init_tree

# f32 matmuls at full f32 precision: the TPU's default rounds f32
# operands to one bf16 pass, which would break the f32 contract the
# serving paths are checked against (a bf16 path is a separate, gated
# change)
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    base_model: str            # sgc | s2gc | sign | gamlp
    feat_dim: int
    num_classes: int
    k: int                     # max propagation order
    r: float = 0.5             # convolution coefficient (Eq. 1)
    hidden: int = 128
    mlp_layers: int = 2        # P in Table 1
    dropout: float = 0.2
    att_dim: int = 32          # GAMLP attention projection

    def input_dim(self, l: int) -> int:
        return self.feat_dim * (l + 1) if self.base_model == "sign" \
            else self.feat_dim


def classifier_defs(cfg: GNNConfig, l: int) -> Dict:
    """MLP head for order l (P=mlp_layers). SGC's paper form is linear —
    mlp_layers=1 reproduces it exactly."""
    dims = [cfg.input_dim(l)] + [cfg.hidden] * (cfg.mlp_layers - 1) \
        + [cfg.num_classes]
    layers = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        layers[f"w{i}"] = ParamDef((a, b), ("feature" if i == 0 else None, None))
        layers[f"b{i}"] = ParamDef((b,), (None,), "zeros")
    if cfg.base_model == "gamlp":
        layers["att_w"] = ParamDef((cfg.feat_dim, cfg.att_dim), ("feature", None), "small")
        layers["att_v"] = ParamDef((cfg.att_dim,), (None,), "small")
    return layers


def all_classifier_defs(cfg: GNNConfig) -> Dict[int, Dict]:
    return {l: classifier_defs(cfg, l) for l in range(1, cfg.k + 1)}


def init_classifiers(cfg: GNNConfig, key) -> Dict[int, Dict]:
    defs = all_classifier_defs(cfg)
    keys = jax.random.split(key, len(defs))
    return {l: init_tree(k, d, "float32")
            for (l, d), k in zip(sorted(defs.items()), keys)}


def _combine(cfg: GNNConfig, feats: jax.Array, l: int, p) -> jax.Array:
    """feats: (k+1, N, f) stacked propagation series X^(0..k)."""
    if cfg.base_model == "sgc":
        return feats[l]
    if cfg.base_model == "s2gc":
        return jnp.mean(feats[:l + 1], axis=0)
    if cfg.base_model == "sign":
        sub = feats[:l + 1]                                   # (l+1, N, f)
        return jnp.moveaxis(sub, 0, 1).reshape(feats.shape[1], -1)
    if cfg.base_model == "gamlp":
        sub = feats[:l + 1]
        scores = jnp.einsum("lnf,fa->lna", sub, p["att_w"],
                            precision=HIGHEST)
        scores = jnp.einsum("lna,a->ln", jax.nn.tanh(scores), p["att_v"],
                            precision=HIGHEST)
        w = jax.nn.softmax(scores, axis=0)                    # (l+1, N)
        return jnp.einsum("ln,lnf->nf", w, sub, precision=HIGHEST)
    raise ValueError(cfg.base_model)


def apply_classifier(cfg: GNNConfig, p, feats, l: int, *,
                     key: Optional[jax.Array] = None) -> jax.Array:
    """Logits of f^(l). feats (k+1, N, f) or (l+1, N, f). `key` enables
    dropout (training)."""
    x = _combine(cfg, jnp.asarray(feats), l, p)
    n_layers = cfg.mlp_layers
    for i in range(n_layers):
        if key is not None and cfg.dropout > 0:
            key, sub = jax.random.split(key)
            mask = jax.random.bernoulli(sub, 1 - cfg.dropout, x.shape)
            x = jnp.where(mask, x / (1 - cfg.dropout), 0.0)
        x = jnp.matmul(x, p[f"w{i}"], precision=HIGHEST) + p[f"b{i}"]
        if i < n_layers - 1:
            x = jax.nn.relu(x)
    return x


def classification_macs(cfg: GNNConfig, l: int) -> int:
    """MACs per node for f^(l) (Table 1 / Table 3 accounting)."""
    dims = [cfg.input_dim(l)] + [cfg.hidden] * (cfg.mlp_layers - 1) \
        + [cfg.num_classes]
    macs = sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    if cfg.base_model == "gamlp":
        macs += (l + 1) * (cfg.feat_dim * cfg.att_dim + cfg.att_dim)
    return macs
