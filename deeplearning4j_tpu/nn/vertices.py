"""Graph vertices — DAG combinators for ComputationGraph.

Parity with DL4J ``org/deeplearning4j/nn/conf/graph/``
(MergeVertex, ElementWiseVertex, SubsetVertex, StackVertex, UnstackVertex,
L2NormalizeVertex, ScaleVertex, ShiftVertex, ReshapeVertex,
PreprocessorVertex) and impls in ``nn/graph/vertex/impl/``.

A vertex is a parameter-free N-ary function over activations (attention
vertices with params are layers here).  JSON round-trip via the same
registry pattern as layers.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from deeplearning4j_tpu.nn.input_type import InputType

_VERTEX_REGISTRY: dict[str, type] = {}


def register_vertex(name: str):
    def deco(cls):
        cls.TYPE_NAME = name
        _VERTEX_REGISTRY[name] = cls
        return cls
    return deco


def vertex_from_dict(d: dict) -> "GraphVertex":
    d = dict(d)
    cls = _VERTEX_REGISTRY[d.pop("type")]
    known = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in d.items() if k in known})


@dataclasses.dataclass
class GraphVertex:
    TYPE_NAME = "vertex"

    def apply(self, inputs: list[jnp.ndarray]) -> jnp.ndarray:
        raise NotImplementedError

    def get_output_type(self, input_types: list[InputType]) -> InputType:
        return input_types[0]

    def to_dict(self) -> dict:
        out = {"type": self.TYPE_NAME}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is not None:
                out[f.name] = v
        return out


@register_vertex("merge")
@dataclasses.dataclass
class MergeVertex(GraphVertex):
    """Concatenate along the channel (last) axis (``MergeVertex.java``;
    reference concatenates along dim 1 = NCHW channels — same semantics,
    NHWC layout)."""

    def apply(self, inputs):
        return jnp.concatenate(inputs, axis=-1)

    def get_output_type(self, input_types):
        t0 = input_types[0]
        if t0.kind == "cnn":
            return InputType.convolutional(t0.height, t0.width,
                                           sum(t.channels for t in input_types))
        if t0.kind == "rnn":
            return InputType.recurrent(sum(t.size for t in input_types), t0.timesteps)
        return InputType.feed_forward(sum(t.flat_size() for t in input_types))


@register_vertex("elementwise")
@dataclasses.dataclass
class ElementWiseVertex(GraphVertex):
    """Pointwise Add/Subtract/Product/Average/Max over equal-shaped inputs
    (``ElementWiseVertex.java``) — the ResNet skip-connection vertex."""

    op: str = "add"

    def apply(self, inputs):
        op = self.op.lower()
        out = inputs[0]
        if op == "add":
            for x in inputs[1:]:
                out = out + x
        elif op in ("subtract", "sub"):
            out = inputs[0] - inputs[1]
        elif op in ("product", "mul"):
            for x in inputs[1:]:
                out = out * x
        elif op in ("average", "avg"):
            out = sum(inputs) / len(inputs)
        elif op == "max":
            for x in inputs[1:]:
                out = jnp.maximum(out, x)
        elif op == "min":
            for x in inputs[1:]:
                out = jnp.minimum(out, x)
        else:
            raise ValueError(f"unknown elementwise op '{self.op}'")
        return out


@register_vertex("subset")
@dataclasses.dataclass
class SubsetVertex(GraphVertex):
    """Channel range [from, to] inclusive (``SubsetVertex.java``)."""

    from_idx: int = 0
    to_idx: int = 0

    def apply(self, inputs):
        return inputs[0][..., self.from_idx:self.to_idx + 1]

    def get_output_type(self, input_types):
        t = input_types[0]
        n = self.to_idx - self.from_idx + 1
        if t.kind == "cnn":
            return InputType.convolutional(t.height, t.width, n)
        if t.kind == "rnn":
            return InputType.recurrent(n, t.timesteps)
        return InputType.feed_forward(n)


@register_vertex("stack")
@dataclasses.dataclass
class StackVertex(GraphVertex):
    """Stack along batch dim (``StackVertex.java``) — pairs with
    UnstackVertex for shared-weight multi-branch tricks."""

    def apply(self, inputs):
        return jnp.concatenate(inputs, axis=0)


@register_vertex("unstack")
@dataclasses.dataclass
class UnstackVertex(GraphVertex):
    """Take slice ``index`` of ``num_stacks`` along batch (``UnstackVertex.java``)."""

    index: int = 0
    num_stacks: int = 1

    def apply(self, inputs):
        x = inputs[0]
        size = x.shape[0] // self.num_stacks
        return x[self.index * size:(self.index + 1) * size]


@register_vertex("l2norm")
@dataclasses.dataclass
class L2NormalizeVertex(GraphVertex):
    """x / ||x||_2 over all non-batch dims (``L2NormalizeVertex.java``)."""

    eps: float = 1e-8

    def apply(self, inputs):
        x = inputs[0]
        axes = tuple(range(1, x.ndim))
        norm = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True))
        return x / jnp.maximum(norm, self.eps)


@register_vertex("scale")
@dataclasses.dataclass
class ScaleVertex(GraphVertex):
    scale: float = 1.0

    def apply(self, inputs):
        return inputs[0] * self.scale


@register_vertex("shift")
@dataclasses.dataclass
class ShiftVertex(GraphVertex):
    shift: float = 0.0

    def apply(self, inputs):
        return inputs[0] + self.shift


@register_vertex("shift_time")
@dataclasses.dataclass
class ShiftTimeVertex(GraphVertex):
    """``y[:, i] = x[:, i + 1]`` along the time axis of [B, T, C], zeros at
    the last step: the embedded sequence one token ahead, which a
    multi-token-prediction module reads beside the main stack's output (no
    second lookup)."""

    def apply(self, inputs):
        x = inputs[0]
        return jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], axis=1)


@register_vertex("attention")
@dataclasses.dataclass
class AttentionVertex(GraphVertex):
    """Multi-head dot-product attention combinator
    (``conf/graph/AttentionVertex.java`` backed by libnd4j
    ``multi_head_dot_product_attention``).

    Inputs: 1 = self-attention over [B,T,H*Dh]; 3 = (queries, keys,
    values) cross-attention.  This vertex is the reference's
    ``projectInput=false`` form — input projections decompose into
    preceding Dense/TimeDistributed layers (the TPU-native factoring:
    each projection is one MXU einsum the compiler fuses anyway)."""

    n_heads: int = 1
    causal: bool = False
    # None = auto: Pallas blockwise kernel at seq >= 1024 (the promoted
    # default); explicit False keeps the einsum chain
    use_flash: Optional[bool] = None
    flash_block: int = 0      # 0 = tuned default (1024×1024 blocks)

    def apply(self, inputs):
        from deeplearning4j_tpu.ops.attention import multi_head_attention
        if len(inputs) == 1:
            q = k = v = inputs[0]
        elif len(inputs) == 3:
            q, k, v = inputs
        else:
            raise ValueError("AttentionVertex takes 1 (self) or 3 (q,k,v) inputs")
        return multi_head_attention(q, k, v, n_heads=self.n_heads,
                                    causal=self.causal,
                                    use_flash=self.use_flash,
                                    flash_block=self.flash_block)

    def get_output_type(self, input_types):
        q, v = input_types[0], input_types[-1]
        return InputType.recurrent(v.size, q.timesteps)   # q steps, v width


@register_vertex("flatten")
@dataclasses.dataclass
class FlattenVertex(GraphVertex):
    """Flatten non-batch dims to a feed-forward vector (the explicit
    twin of the lazy cnn→ff preprocessor — needed when a downstream
    consumer like a merge vertex accepts any rank, so the implicit
    adaptation would never fire; used by the Keras Functional importer
    for explicit ``Flatten`` nodes)."""

    def apply(self, inputs):
        x = inputs[0]
        return x.reshape(x.shape[0], -1)

    def get_output_type(self, input_types):
        return InputType.feed_forward(input_types[0].flat_size())


@register_vertex("reshape")
@dataclasses.dataclass
class ReshapeVertex(GraphVertex):
    """Reshape non-batch dims (``ReshapeVertex.java``)."""

    shape: Optional[list] = None  # without batch dim

    def apply(self, inputs):
        x = inputs[0]
        return x.reshape((x.shape[0],) + tuple(self.shape))

    def get_output_type(self, input_types):
        s = tuple(self.shape)
        if len(s) == 1:
            return InputType.feed_forward(s[0])
        if len(s) == 2:
            return InputType.recurrent(s[1], s[0])
        if len(s) == 3:
            return InputType.convolutional(s[0], s[1], s[2])
        raise ValueError(f"unsupported reshape target {s}")
