"""ComputationGraph — the DAG network.

Parity with DL4J ``org/deeplearning4j/nn/graph/ComputationGraph.java`` +
``conf/ComputationGraphConfiguration.java`` (GraphBuilder): named vertices
(layers or combinator vertices), multiple inputs and outputs, topological
execution.  The topo order is computed once at build; the traversal is a
static Python loop that traces into ONE fused XLA program under jit, so
the reference's per-vertex dispatch disappears.

Supports multi-input/multi-output training with MultiDataSet (losses from
all output layers are summed, ``ComputationGraph.fit(MultiDataSet)``
parity).
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf import ShapeInferenceError
from deeplearning4j_tpu.nn.input_type import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, layer_from_dict
from deeplearning4j_tpu.nn.vertices import GraphVertex, vertex_from_dict
from deeplearning4j_tpu.nn import preprocessors
from deeplearning4j_tpu.train import updaters as updater_mod
from deeplearning4j_tpu.utils.pytree import flat_param_vector, param_count


@dataclasses.dataclass
class VertexSpec:
    name: str
    kind: str            # "layer" | "vertex"
    obj: Any             # Layer or GraphVertex
    inputs: list         # names of input vertices / graph inputs

    def to_dict(self):
        return {"name": self.name, "kind": self.kind, "obj": self.obj.to_dict(),
                "inputs": list(self.inputs)}

    @staticmethod
    def from_dict(d):
        obj = layer_from_dict(d["obj"]) if d["kind"] == "layer" else vertex_from_dict(d["obj"])
        return VertexSpec(d["name"], d["kind"], obj, list(d["inputs"]))


@dataclasses.dataclass
class ComputationGraphConfiguration:
    inputs: list = dataclasses.field(default_factory=list)
    outputs: list = dataclasses.field(default_factory=list)
    vertices: list = dataclasses.field(default_factory=list)  # [VertexSpec] topo-insertable order
    input_types: list = dataclasses.field(default_factory=list)
    seed: int = 0
    updater: Any = None
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    mini_batch: bool = True
    backprop_type: str = "standard"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    # [[first, last], ...]: runs of vertices (inclusive, contiguous in the
    # topological order) whose activations the backward pass recomputes
    # from the run's inputs instead of keeping; a flash kernel's output and
    # row statistics are kept all the same (GraphBuilder.remat)
    remat_segments: list = dataclasses.field(default_factory=list)

    # ---------------------------------------------------------- topo/types
    def topo_order(self) -> list[VertexSpec]:
        by_name = {v.name: v for v in self.vertices}
        resolved: dict[str, bool] = {name: True for name in self.inputs}
        order: list[VertexSpec] = []
        pending = list(self.vertices)
        while pending:
            progressed = False
            remaining = []
            for spec in pending:
                if all(i in resolved for i in spec.inputs):
                    order.append(spec)
                    resolved[spec.name] = True
                    progressed = True
                else:
                    remaining.append(spec)
            if not progressed:
                missing = {i for s in remaining for i in s.inputs if i not in resolved}
                raise ValueError(f"graph has unresolvable inputs or a cycle: {missing}")
            pending = remaining
        return order

    def vertex_input_types(self) -> dict[str, list[InputType]]:
        """Name → list of InputTypes arriving at that vertex (post-adaptation
        for layers, raw for vertices)."""
        if len(self.input_types) != len(self.inputs):
            raise ValueError("set_input_types must provide one InputType per graph input")
        known: dict[str, InputType] = dict(zip(self.inputs, self.input_types))
        result: dict[str, list[InputType]] = {}
        for spec in self.topo_order():
            try:
                in_types = [known[i] for i in spec.inputs]
                if spec.kind == "layer":
                    adapted = [preprocessors.adapt_type(in_types[0], spec.obj)]
                    result[spec.name] = adapted
                    known[spec.name] = spec.obj.get_output_type(adapted[0])
                else:
                    result[spec.name] = in_types
                    known[spec.name] = spec.obj.get_output_type(in_types)
            except ShapeInferenceError:
                raise
            except Exception as e:
                raise ShapeInferenceError(
                    f"vertex '{spec.name}' ({type(spec.obj).__name__})", e) from e
        return result

    def output_types(self) -> dict[str, InputType]:
        if len(self.input_types) != len(self.inputs):
            raise ValueError("set_input_types must provide one InputType per graph input")
        known = dict(zip(self.inputs, self.input_types))
        for spec in self.topo_order():
            try:
                in_types = [known[i] for i in spec.inputs]
                if spec.kind == "layer":
                    known[spec.name] = spec.obj.get_output_type(
                        preprocessors.adapt_type(in_types[0], spec.obj))
                else:
                    known[spec.name] = spec.obj.get_output_type(in_types)
            except ShapeInferenceError:
                raise
            except Exception as e:
                raise ShapeInferenceError(
                    f"vertex '{spec.name}' ({type(spec.obj).__name__})", e) from e
        return {name: known[name] for name in self.outputs}

    # ---------------------------------------------------------- serde
    def to_dict(self):
        return {
            "inputs": self.inputs,
            "outputs": self.outputs,
            "vertices": [v.to_dict() for v in self.vertices],
            "input_types": [t.to_dict() for t in self.input_types],
            "seed": self.seed,
            "updater": updater_mod.to_dict(self.updater) if self.updater else None,
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold": self.gradient_normalization_threshold,
            "mini_batch": self.mini_batch,
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            **({"remat_segments": [list(s) for s in self.remat_segments]}
               if self.remat_segments else {}),
        }

    def to_json(self):
        return json.dumps(self.to_dict(), indent=2)

    @staticmethod
    def from_dict(d):
        return ComputationGraphConfiguration(
            inputs=list(d["inputs"]),
            outputs=list(d["outputs"]),
            vertices=[VertexSpec.from_dict(v) for v in d["vertices"]],
            input_types=[InputType.from_dict(t) for t in d["input_types"]],
            seed=d.get("seed", 0),
            updater=updater_mod.from_dict(d["updater"]) if d.get("updater") else None,
            gradient_normalization=d.get("gradient_normalization"),
            gradient_normalization_threshold=d.get("gradient_normalization_threshold", 1.0),
            mini_batch=d.get("mini_batch", True),
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
            remat_segments=[list(s) for s in d.get("remat_segments", [])],
        )

    @staticmethod
    def from_json(s):
        return ComputationGraphConfiguration.from_dict(json.loads(s))


def _remat_keeps() -> tuple:
    """The names a rematerialised run keeps beside its inputs: the flash
    kernel's two residuals (imported here: a graph without runs never
    loads Pallas)."""
    from deeplearning4j_tpu.ops.pallas.flash_attention import REMAT_KEEPS
    return REMAT_KEEPS


class GraphBuilder:
    """``ComputationGraphConfiguration.GraphBuilder`` parity."""

    def __init__(self, parent):
        self.parent = parent  # nn.conf.Builder carrying global defaults
        self._inputs: list[str] = []
        self._outputs: list[str] = []
        self._vertices: list[VertexSpec] = []
        self._input_types: list[InputType] = []
        self._backprop_type = "standard"
        self._tbptt = (20, 20)
        self._remat: list[list[str]] = []

    def remat(self, first: str, last: str) -> "GraphBuilder":
        """Rematerialise the vertices ``first`` .. ``last`` (as added, both
        included) in the backward pass: the run's inputs are kept through
        the forward pass and ``jax.checkpoint`` recomputes the rest, but
        for what only a kernel launch can rebuild: a ``flash_attention``
        call inside the run keeps its output and its rows' logsumexp
        (``[B,H,T,Dv]`` and ``[B,H,T]``, 68 MB at 32 heads of 8k tokens),
        so the kernel runs once a step and not twice.  A run with no such
        call keeps its inputs alone.  One run a decoder block is what lets
        8k tokens fit a chip."""
        self._remat.append([first, last])
        return self

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._inputs.extend(names)
        return self

    def set_input_types(self, *types: InputType) -> "GraphBuilder":
        self._input_types.extend(types)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str) -> "GraphBuilder":
        self._vertices.append(VertexSpec(name, "layer", layer, list(inputs)))
        return self

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str) -> "GraphBuilder":
        self._vertices.append(VertexSpec(name, "vertex", vertex, list(inputs)))
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._outputs.extend(names)
        return self

    def backprop_type(self, kind: str, fwd: int = 20, back: int = 20) -> "GraphBuilder":
        self._backprop_type = kind
        self._tbptt = (fwd, back)
        return self

    def build(self) -> ComputationGraphConfiguration:
        p = self.parent
        for spec in self._vertices:
            if spec.kind == "layer":
                spec.obj.inherit_defaults(p._defaults)
        conf = ComputationGraphConfiguration(
            inputs=self._inputs, outputs=self._outputs, vertices=self._vertices,
            input_types=self._input_types, seed=p._seed, updater=p._updater,
            gradient_normalization=p._grad_norm,
            gradient_normalization_threshold=p._grad_norm_threshold,
            mini_batch=p._mini_batch,
            backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt[0], tbptt_back_length=self._tbptt[1],
            remat_segments=self._remat,
        )
        conf.topo_order()  # validate DAG now
        return conf


class ComputationGraph:
    """DAG network with the MultiLayerNetwork-compatible training surface
    (Trainer drives both through ``_forward``/``layers``)."""

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self._topo = conf.topo_order()
        self.params_: Optional[dict] = None   # name → params dict
        self.state_: Optional[dict] = None
        self.opt_state = None
        self.iteration = 0
        self.epoch = 0
        self._score = float("nan")
        self._output_fn = None

    # Trainer compatibility: iterate layer objects + parallel params
    @property
    def layers(self) -> list:
        return [s.obj for s in self._topo if s.kind == "layer"]

    def layer_params(self, params) -> list:
        return [params[s.name] for s in self._topo if s.kind == "layer"]

    # ------------------------------------------------------------- init
    def init(self, seed: Optional[int] = None) -> "ComputationGraph":
        seed = self.conf.seed if seed is None else seed
        key = jax.random.key(seed)
        in_types = self.conf.vertex_input_types()
        self.params_, self.state_ = {}, {}
        for spec in self._topo:
            if spec.kind == "layer":
                key, sub = jax.random.split(key)
                itype = in_types[spec.name][0]
                self.params_[spec.name] = (spec.obj.init_params(sub, itype)
                                           if spec.obj.has_params() else {})
                self.state_[spec.name] = spec.obj.init_state(itype)
            else:
                self.params_[spec.name] = {}
                self.state_[spec.name] = {}
        return self

    def num_params(self) -> int:
        return param_count(self.params_)

    @staticmethod
    def _owner(spec: VertexSpec) -> str:
        """The vertex whose parameters ``spec`` runs on: its own, or for a
        layer tied to another (``tie_to``, a tied output head) that one's,
        so that one leaf serves both and its gradient sums their uses."""
        return getattr(spec.obj, "tie_to", "") or spec.name

    def params(self) -> jnp.ndarray:
        return flat_param_vector(self.params_)

    # ---------------------------------------------------------- forward
    def _remat_plan(self) -> dict:
        """``conf.remat_segments`` against the topological order: {index of
        a run's first vertex: (index of its last, names it reads from
        outside, names read after it)}.  Empty for a graph without runs."""
        if not self.conf.remat_segments:
            return {}
        index = {spec.name: i for i, spec in enumerate(self._topo)}
        plan = {}
        for first, last in self.conf.remat_segments:
            a, b = index[first], index[last]
            inside = {spec.name for spec in self._topo[a:b + 1]}
            if a > b or inside & set(self.conf.outputs):
                raise ValueError(
                    f"remat run {first!r}..{last!r} is empty or holds an "
                    f"output vertex")
            reads = sorted({i for spec in self._topo[a:b + 1]
                            for i in spec.inputs} - inside)
            after = {i for spec in self._topo[b + 1:] for i in spec.inputs}
            plan[a] = (b, reads, sorted(inside & after))
        return plan

    def _forward(self, params, state, features, *, train: bool, rng=None,
                 mask=None, labels=None):
        """features: array (single input) or tuple/list (multi input);
        labels: array or list aligned with conf.outputs.  Returns
        (outputs, new_state, score_array) where outputs is an array for a
        single graph output, else a list."""
        feats = list(features) if isinstance(features, (list, tuple)) else [features]
        masks = list(mask) if isinstance(mask, (list, tuple)) else [mask] * len(feats)
        label_list = (list(labels) if isinstance(labels, (list, tuple))
                      else [labels] * len(self.conf.outputs)) if labels is not None else None

        acts: dict[str, Any] = dict(zip(self.conf.inputs, feats))
        act_masks: dict[str, Any] = dict(zip(self.conf.inputs, masks))
        known_types = dict(zip(self.conf.inputs, self.conf.input_types))
        new_state = {}
        score_arrays = []

        def run(vi, spec, acts, act_masks, params, state, rng):
            """One vertex: its activation and mask into ``acts`` and
            ``act_masks``, its new state returned."""
            in_acts = [acts[i] for i in spec.inputs]
            in_mask = next((act_masks.get(i) for i in spec.inputs
                            if act_masks.get(i) is not None), None)
            # the vertex's name on every device operation it lowers to:
            # what obs.profiler.timeline sums device time by
            with jax.named_scope(spec.name):
                if spec.kind == "layer":
                    layer_rng = jax.random.fold_in(rng, vi) if rng is not None else None
                    itype = known_types[spec.inputs[0]]
                    x = preprocessors.adapt_array(in_acts[0], itype, spec.obj)
                    own = params[self._owner(spec)]
                    if (labels is not None and spec.name in self.conf.outputs
                            and hasattr(spec.obj, "compute_score_array")):
                        out_idx = self.conf.outputs.index(spec.name)
                        # same noised weights as apply(): IWeightNoise applies
                        # to the loss path too (DL4J BaseLayer.getParamWithNoise)
                        score_arrays.append(spec.obj.compute_score_array(
                            spec.obj.noised_params(own, train, layer_rng),
                            state[spec.name], x,
                            label_list[out_idx], train=train, rng=layer_rng,
                            mask=in_mask))
                    y, s = spec.obj.apply(
                        spec.obj.noised_params(own, train, layer_rng),
                        state[spec.name], x,
                        train=train, rng=layer_rng, mask=in_mask)
                    known_types[spec.name] = spec.obj.get_output_type(
                        preprocessors.adapt_type(itype, spec.obj))
                else:
                    y = spec.obj.apply(in_acts)
                    s = state[spec.name]
                    known_types[spec.name] = spec.obj.get_output_type(
                        [known_types[i] for i in spec.inputs])
            acts[spec.name] = y
            act_masks[spec.name] = in_mask
            return s

        segments = self._remat_plan() if train else {}
        vi = 0
        while vi < len(self._topo):
            spec = self._topo[vi]
            if vi not in segments:
                new_state[spec.name] = run(vi, spec, acts, act_masks, params,
                                           state, rng)
                vi += 1
                continue
            # a rematerialised run: what it reads from outside goes in,
            # what is read after it (and its layers' new state) comes out
            last, reads, keeps = segments[vi]
            specs = list(enumerate(self._topo[vi:last + 1], vi))
            names = [sp.name for _, sp in specs]
            owners = names + sorted({self._owner(sp) for _, sp in specs}
                                    - set(names))

            def segment(seg_params, seg_state, seg_acts, seg_masks, seg_rng):
                local, local_masks, states = dict(seg_acts), dict(seg_masks), {}
                for i, sp in specs:
                    states[sp.name] = run(i, sp, local, local_masks,
                                          seg_params, seg_state, seg_rng)
                return ({n: local[n] for n in keeps},
                        {n: local_masks[n] for n in keeps}, states)

            kept, kept_masks, states = jax.checkpoint(
                segment, policy=jax.checkpoint_policies.save_only_these_names(
                    *_remat_keeps()))(
                {n: params[n] for n in owners}, {n: state[n] for n in names},
                {n: acts[n] for n in reads},
                {n: act_masks.get(n) for n in reads}, rng)
            acts.update(kept)
            act_masks.update(kept_masks)
            new_state.update(states)
            vi = last + 1
        outs = [acts[name] for name in self.conf.outputs]
        score_array = None
        if score_arrays:
            score_array = score_arrays[0]
            for extra in score_arrays[1:]:
                score_array = score_array + extra
        return (outs[0] if len(outs) == 1 else outs), new_state, score_array

    def output(self, *features, mask=None):
        if self._output_fn is None:
            @jax.jit
            def _out(params, state, features, mask):
                y, _, _ = self._forward(params, state, features, train=False, mask=mask)
                return y
            self._output_fn = _out
        feats = features[0] if len(features) == 1 else tuple(jnp.asarray(f) for f in features)
        return self._output_fn(self.params_, self.state_, feats, mask)

    # ---------------------------------------------------------- training
    def score(self) -> float:
        return float(self._score)

    def fit(self, iterator, epochs: int = 1, listeners=None,
            resume_from=None):
        from deeplearning4j_tpu.train.trainer import Trainer
        Trainer(self, listeners=listeners).fit(iterator, epochs,
                                               resume_from=resume_from)
        return self

    def trace_attrs(self) -> dict:
        """Model identity attached to the trainer's ``fit`` span
        (``obs.tracing``) — what a trace viewer shows for this run."""
        runs = len(self.conf.remat_segments)
        attrs = {"model": "ComputationGraph",
                 "vertices": len(self._topo),
                 "layers": len(self.layers),
                 "params": self.num_params() if self.params_ is not None
                 else 0,
                 "remat_runs": runs,
                 "remat_keeps": list(_remat_keeps()) if runs else []}
        # a decoder's attention layers name their kind, in order; a model
        # without any carries neither key
        attention = [layer for layer in self.layers
                     if hasattr(layer, "ATTENTION_KIND")]
        if attention:
            attrs["attention_kinds"] = [layer.ATTENTION_KIND
                                        for layer in attention]
        groups = sorted({layer.n_heads // layer.n_kv_heads
                         for layer in attention
                         if getattr(layer, "n_kv_heads", 0)})
        if groups:          # query heads a K/V head serves
            attrs["kv_groups"] = groups[0] if len(groups) == 1 else groups
        delta = [layer for layer in attention if layer.ATTENTION_KIND == "kda"]
        chunks = sorted({layer.chunk for layer in delta})
        if chunks:
            attrs["kda_chunk"] = chunks[0] if len(chunks) == 1 else chunks
        for key, kind in (("kda_kernel", "kernel"),
                          ("kda_bwd_kernel", "bwd_kernel")):
            kernels = sorted({getattr(layer, kind) for layer in delta}
                             - {None})
            if kernels:
                attrs[key] = kernels[0]
        return attrs

    def evaluate(self, iterator, top_n: int = 1):
        from deeplearning4j_tpu.evaluation.classification import Evaluation
        evaluation = Evaluation(top_n=top_n)
        for batch in iterator:
            out = self.output(batch.features, mask=batch.features_mask)
            out0 = out[0] if isinstance(out, list) else out
            labels = batch.labels[0] if isinstance(batch.labels, (list, tuple)) else batch.labels
            evaluation.eval(labels, np.asarray(out0), mask=batch.labels_mask)
        return evaluation

    # ---------------------------------------------------------- serde
    def save(self, path: str, save_updater: bool = True,
             iterator_state=None, normalizer=None) -> None:
        from deeplearning4j_tpu.io.model_serializer import write_model
        write_model(self, path, save_updater=save_updater,
                    iterator_state=iterator_state, normalizer=normalizer)

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "ComputationGraph":
        from deeplearning4j_tpu.io.model_serializer import restore_computation_graph
        return restore_computation_graph(path, load_updater=load_updater)

    def summary(self) -> str:
        types = self.conf.vertex_input_types()
        out_types = {}
        lines = [f"{'name':<20}{'kind':<22}{'inputs':<28}{'params':<10}"]
        for spec in self._topo:
            n = param_count(self.params_[spec.name]) if self.params_ else 0
            kind = spec.obj.TYPE_NAME
            lines.append(f"{spec.name:<20}{kind:<22}{','.join(spec.inputs):<28}{n:<10}")
        lines.append(f"Total params: {self.num_params() if self.params_ else 0}")
        return "\n".join(lines)
