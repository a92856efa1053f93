"""Base layer dataclass + JSON-subtype registry.

Each layer config is a dataclass whose fields are its hyperparameters; the
implementation is two pure functions:

- ``init_params(key, input_type) -> params`` — build the parameter dict
  (``ParamInitializer`` parity, deeplearning4j-nn ``nn/params/``).
- ``apply(params, state, x, *, train, rng) -> (y, new_state)`` — forward
  (``Layer.activate`` parity); ``state`` holds non-trainable variables
  (batch-norm running stats); backward is jax autodiff.

Global defaults from ``NeuralNetConfiguration`` cascade into unset fields
(`None` sentinel), matching DL4J's builder semantics where e.g.
``.activation(...)`` at the net level applies to layers that don't override.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn import weights as weight_inits
from deeplearning4j_tpu.ops.attention import dropout

_LAYER_REGISTRY: dict[str, type] = {}


def register_layer(type_name: str):
    """JSON-subtype registration (DL4J ``@JsonSubTypes`` / custom-layer SPI
    parity).  User layers register the same way builtin ones do."""
    def deco(cls):
        cls.TYPE_NAME = type_name
        _LAYER_REGISTRY[type_name] = cls
        return cls
    return deco


def layer_registry() -> dict[str, type]:
    return dict(_LAYER_REGISTRY)


def layer_from_dict(d: dict) -> "Layer":
    from deeplearning4j_tpu.train import updaters as updater_mod
    d = dict(d)
    type_name = d.pop("type")
    cls = _LAYER_REGISTRY.get(type_name)
    if cls is None:
        raise KeyError(f"unknown layer type '{type_name}'; registered: {sorted(_LAYER_REGISTRY)}")
    if isinstance(d.get("updater"), dict):
        d["updater"] = updater_mod.from_dict(d["updater"])
    if isinstance(d.get("weight_noise"), dict):
        from deeplearning4j_tpu.nn import weight_noise as wn_mod
        d["weight_noise"] = wn_mod.from_dict(d["weight_noise"])
    known = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in d.items() if k in known})


@dataclasses.dataclass
class Layer:
    """Base config.  ``None`` fields inherit the network-level default.

    - ``dropout`` follows DL4J semantics: it is the RETAIN probability
      (``layer.dropOut(0.8)`` keeps 80% of activations), applied to the
      layer's INPUT during training with inverted scaling.
    - ``l1``/``l2`` apply to weights; ``l1_bias``/``l2_bias`` to biases.
    """

    TYPE_NAME = "base"

    name: Optional[str] = None
    activation: Optional[Any] = None
    weight_init: Optional[Any] = None
    bias_init: Optional[float] = None
    dropout: Optional[float] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    l1_bias: Optional[float] = None
    l2_bias: Optional[float] = None
    updater: Optional[Any] = None   # per-layer updater override (DL4J allows it)
    frozen: bool = False            # FrozenLayer parity: excluded from updates
    # IWeightNoise parity: DropConnect / WeightNoise applied to the
    # weights on training forward passes (nn/weight_noise.py)
    weight_noise: Optional[Any] = None

    # ---- conf API ----------------------------------------------------
    def inherit_defaults(self, defaults: dict) -> None:
        for field, value in defaults.items():
            if hasattr(self, field) and getattr(self, field) is None:
                setattr(self, field, value)

    def has_params(self) -> bool:
        return True

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    def to_dict(self) -> dict:
        from deeplearning4j_tpu.train import updaters as updater_mod
        from deeplearning4j_tpu.nn import weight_noise as wn_mod
        out = {"type": self.TYPE_NAME}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is None or callable(v):
                continue
            if f.name == "updater":
                v = updater_mod.to_dict(v)
            elif f.name == "weight_noise":
                v = wn_mod.to_dict(v)
            out[f.name] = v
        return out

    # ---- impl API ----------------------------------------------------
    def init_params(self, key: jax.Array, input_type: InputType) -> dict:
        return {}

    def init_state(self, input_type: InputType) -> dict:
        return {}

    def apply(self, params: dict, state: dict, x: jnp.ndarray, *,
              train: bool = False, rng: Optional[jax.Array] = None,
              mask: Optional[jnp.ndarray] = None):
        raise NotImplementedError

    def transform_mask(self, mask: Optional[jnp.ndarray]):
        """How this layer reshapes a per-timestep [B,T] mask
        (``Layer.feedForwardMaskArray`` parity).  Default: unchanged.
        Layers that change the time axis override; layers that destroy
        the timestep correspondence return None."""
        return mask

    # ---- shared helpers ---------------------------------------------
    def _param_dtype(self):
        """Storage dtype for THIS layer's params (DTypePolicy.param_dtype) —
        every init_params allocation must use it so param trees stay
        uniform-dtype for checkpoints and updaters."""
        from deeplearning4j_tpu.config import dtype_policy
        return dtype_policy().param_dtype

    def _init_weight(self, key, shape, fan_in, fan_out, dtype=None):
        if dtype is None:
            dtype = self._param_dtype()
        init = weight_inits.get(self.weight_init or "xavier")
        return init(key, shape, float(fan_in), float(fan_out), dtype)

    def _init_bias(self, shape, dtype=None):
        if dtype is None:
            dtype = self._param_dtype()
        return jnp.full(shape, self.bias_init if self.bias_init is not None else 0.0, dtype)

    def noised_params(self, params: dict, train: bool, rng) -> dict:
        """Weight-noise hook (IWeightNoise parity): on training passes
        with ``weight_noise`` configured, return a transformed COPY of
        the params; inference and noise-free layers pass through."""
        if (not train or self.weight_noise is None or rng is None
                or not params):
            return params
        from deeplearning4j_tpu.nn import weight_noise as wn_mod
        return wn_mod.apply_noise(self.weight_noise, params,
                                  jax.random.fold_in(rng, 0x5EED))

    def _maybe_dropout(self, x, train, rng):
        """Input dropout with DL4J retain-probability semantics."""
        p = self.dropout
        if not train or p is None or p >= 1.0 or rng is None:
            return x
        return dropout(x, p, rng)

    def regularization_penalty(self, params: dict) -> jnp.ndarray:
        """L1/L2 penalty for this layer's params (DL4J applies l2*w to the
        gradient, i.e. a 0.5*l2*||w||^2 score term; biases use the *_bias
        coefficients)."""
        penalty = jnp.float32(0.0)
        for pname, arr in params.items():
            is_bias = pname == "b" or pname.endswith("_b") or "bias" in pname
            l1 = (self.l1_bias if is_bias else self.l1) or 0.0
            l2 = (self.l2_bias if is_bias else self.l2) or 0.0
            if l1:
                penalty = penalty + l1 * jnp.sum(jnp.abs(arr))
            if l2:
                penalty = penalty + 0.5 * l2 * jnp.sum(arr * arr)
        return penalty
