"""Norm layers (python/paddle/nn/layer/norm.py parity)."""
from __future__ import annotations

import jax.numpy as jnp

from ...core.tensor import Tensor
from .. import functional as F
from .. import initializer as I
from .layers import Layer

__all__ = ["SpectralNorm", "BatchNorm", "BatchNorm1D", "BatchNorm2D", "BatchNorm3D",
           "SyncBatchNorm", "LayerNorm", "RMSNorm", "GroupNorm", "InstanceNorm1D",
           "InstanceNorm2D", "InstanceNorm3D", "LocalResponseNorm"]


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        self.weight = self.create_parameter(
            shape=[num_features], attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter(
            shape=[num_features], attr=bias_attr, is_bias=True)
        self.register_buffer("_mean", Tensor(jnp.zeros(num_features,
                                                       dtype=self._dtype)))
        self.register_buffer("_variance", Tensor(jnp.ones(num_features,
                                                          dtype=self._dtype)))

    def forward(self, x):
        return F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self._momentum,
            epsilon=self._epsilon, data_format=self._data_format,
            use_global_stats=self._use_global_stats)

    def extra_repr(self):
        return f"num_features={self._num_features}, momentum={self._momentum}"


class BatchNorm(_BatchNormBase):
    """fluid.dygraph.BatchNorm-compatible alias."""


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05,
                 weight_attr=None, bias_attr=None, data_format="NCL",
                 use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, data_format, use_global_stats)


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-05,
                 weight_attr=None, bias_attr=None, data_format="NCDHW",
                 use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, data_format, use_global_stats)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica BN (reference: operators/sync_batch_norm_op.cu).

    TPU-native: under SPMD the batch axis is sharded over the mesh; statistics
    are computed with a psum over the data axis when inside a shard_map region
    (distributed/parallel.py wires this); otherwise falls back to local BN.
    """

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        out = layer
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, SyncBatchNorm):
            sync = SyncBatchNorm(layer._num_features, layer._momentum,
                                 layer._epsilon, data_format=layer._data_format)
            sync.weight = layer.weight
            sync.bias = layer.bias
            sync.register_buffer("_mean", layer._mean)
            sync.register_buffer("_variance", layer._variance)
            return sync
        for name, sub in layer.named_children():
            layer._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return out


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-05, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            shape=self._normalized_shape, attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter(
            shape=self._normalized_shape, attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.layer_norm(x, self._normalized_shape, self.weight, self.bias,
                            self._epsilon)

    def extra_repr(self):
        return f"normalized_shape={self._normalized_shape}"


class RMSNorm(Layer):
    """Root-mean-square norm over the last axis with a learned gain and no
    bias (F.rms_norm)."""

    def __init__(self, hidden_size, epsilon=1e-05, weight_attr=None,
                 name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            shape=[hidden_size], attr=weight_attr,
            default_initializer=I.Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self._epsilon)

    def extra_repr(self):
        return f"hidden_size={self.weight.shape[0]}, epsilon={self._epsilon}"


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-05,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._num_groups = num_groups
        self._num_channels = num_channels
        self._epsilon = epsilon
        self._data_format = data_format
        self.weight = self.create_parameter(
            shape=[num_channels], attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter(
            shape=[num_channels], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.group_norm(x, self._num_groups, self._epsilon, self.weight,
                            self.bias, self._data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-05, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self._epsilon = epsilon
        self._data_format = data_format
        if weight_attr is False or bias_attr is False:
            self.scale = None
            self.bias = None
        else:
            self.scale = self.create_parameter(
                shape=[num_features], attr=weight_attr,
                default_initializer=I.Constant(1.0))
            self.bias = self.create_parameter(
                shape=[num_features], attr=bias_attr, is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, weight=self.scale, bias=self.bias,
                               eps=self._epsilon,
                               data_format=self._data_format)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=0.0001, beta=0.75, k=1.0,
                 data_format="NCHW", name=None):
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k
        self.data_format = data_format

    def forward(self, x):
        return F.local_response_norm(x, self.size, self.alpha, self.beta,
                                     self.k, self.data_format)


class SpectralNorm(Layer):
    """Spectral normalization of a weight tensor (reference
    nn/layer/norm.py SpectralNorm over operators/spectral_norm_op.*):
    power-iteration estimate of the largest singular value; forward returns
    weight / sigma."""

    def __init__(self, weight_shape, dim=0, power_iters=1, eps=1e-12,
                 name=None, dtype="float32"):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._eps = eps
        h = weight_shape[dim]
        w = 1
        for i, s in enumerate(weight_shape):
            if i != dim:
                w *= s
        self.weight_u = self.create_parameter(
            [h], default_initializer=I.Normal(0.0, 1.0))
        self.weight_v = self.create_parameter(
            [w], default_initializer=I.Normal(0.0, 1.0))
        self.weight_u.stop_gradient = True
        self.weight_v.stop_gradient = True

    def forward(self, weight):
        from ...core.dispatch import apply
        dim, iters, eps = self._dim, self._power_iters, self._eps

        def prim(wt, u, v):
            import jax
            perm = (dim,) + tuple(i for i in range(wt.ndim) if i != dim)
            mat = jnp.transpose(wt, perm).reshape(wt.shape[dim], -1)
            # power iteration runs OUTSIDE the grad path: the reference op
            # treats the saved u/v as constants when differentiating
            # sigma = u^T W v (spectral_norm_op grad kernel)
            mat_sg = jax.lax.stop_gradient(mat)
            uu, vv = u, v
            for _ in range(iters):
                vv = mat_sg.T @ uu
                vv = vv / (jnp.linalg.norm(vv) + eps)
                uu = mat_sg @ vv
                uu = uu / (jnp.linalg.norm(uu) + eps)
            uu = jax.lax.stop_gradient(uu)
            vv = jax.lax.stop_gradient(vv)
            sigma = uu @ mat @ vv
            return wt / sigma, uu, vv

        out, u_new, v_new = apply(prim, weight, self.weight_u, self.weight_v,
                                  name="spectral_norm")
        self.weight_u._value = u_new._value
        self.weight_v._value = v_new._value
        return out
