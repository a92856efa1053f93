"""Weight noise — DropConnect and additive/multiplicative Gaussian.

Parity: DL4J ``nn/conf/weightnoise/`` (``IWeightNoise``, ``DropConnect``,
``WeightNoise``): a per-layer transform applied to the WEIGHTS (not the
activations) on every training forward pass; inference uses the clean
weights.  TPU-native: the transform is pure jnp inside the jit step
(per-step bernoulli/normal from the layer's fold_in'd rng), so it fuses
into the layer's matmul read — no extra HBM pass.

Config on any layer: ``DenseLayer(..., weight_noise=DropConnect(0.9))``;
serializes through the layer JSON round trip like updaters.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.attention import dropout

_REGISTRY: dict[str, type] = {}


def register(name: str):
    def deco(cls):
        cls.TYPE_NAME = name
        _REGISTRY[name] = cls
        return cls
    return deco


def to_dict(noise) -> Optional[dict]:
    if noise is None:
        return None
    out = {"type": noise.TYPE_NAME}
    out.update(dataclasses.asdict(noise))
    return out


def from_dict(d) -> Optional[object]:
    if d is None:
        return None
    if not isinstance(d, dict):
        return d                      # already an instance
    d = dict(d)
    cls = _REGISTRY[d.pop("type")]
    known = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in d.items() if k in known})


def _is_bias(pname: str) -> bool:
    return pname == "b" or pname.endswith("_b") or "bias" in pname


def apply_noise(noise, params: dict, rng) -> dict:
    """Transform each eligible param with a param-specific rng stream."""
    out = {}
    for i, (pname, arr) in enumerate(sorted(params.items())):
        if _is_bias(pname) and not noise.apply_to_bias:
            out[pname] = arr
        else:
            out[pname] = noise.transform(arr, jax.random.fold_in(rng, i))
    return out


@register("drop_connect")
@dataclasses.dataclass
class DropConnect:
    """Drop individual weights with probability 1-p during training
    (``weightnoise/DropConnect.java``; p is the RETAIN probability,
    matching DL4J's dropout convention), with inverted scaling so the
    expected pre-activation is unchanged."""

    p: float = 0.5
    apply_to_bias: bool = False

    def transform(self, w, rng):
        return dropout(w, self.p, rng)


@register("weight_noise")
@dataclasses.dataclass
class WeightNoise:
    """Gaussian weight noise (``weightnoise/WeightNoise.java`` with a
    NormalDistribution): additive w + N(mean, stddev) or multiplicative
    w * N(mean, stddev)."""

    mean: float = 0.0
    stddev: float = 0.01
    additive: bool = True
    apply_to_bias: bool = False

    def transform(self, w, rng):
        noise = (self.mean
                 + self.stddev * jax.random.normal(rng, w.shape, jnp.float32))
        out = w + noise if self.additive else w * noise
        return out.astype(w.dtype)
