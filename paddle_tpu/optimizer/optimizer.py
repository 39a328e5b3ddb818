"""Optimizer base (python/paddle/optimizer/optimizer.py:49 parity).

TPU-native design: hyperparameters that vary over time (lr, beta powers, step
count) are held in Tensors so a jitted train step captures them as state — the
compiled XLA computation stays valid across lr-schedule changes and step
increments (no retrace). Accumulators are Tensors created lazily per param
(mirrors _create_accumulators / _add_accumulator in the reference).
"""
from __future__ import annotations

from collections import defaultdict

import jax
import jax.numpy as jnp

from ..core import autograd
from ..core.dispatch import unwrap
from ..core.tensor import Tensor
from ..nn.clip import ClipGradBase

__all__ = ["Optimizer"]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        from .lr import LRScheduler
        self._parameter_list = list(parameters) if parameters is not None else None
        self._param_groups = None
        if self._parameter_list and isinstance(self._parameter_list[0], dict):
            self._param_groups = self._parameter_list
            flat = []
            for g in self._param_groups:
                flat += list(g["params"])
            self._parameter_list = flat
        self._lr_scheduler = None
        if isinstance(learning_rate, LRScheduler):
            self._lr_scheduler = learning_rate
            lr0 = float(learning_rate())
        else:
            lr0 = float(learning_rate)
        self._learning_rate = Tensor(jnp.asarray(lr0, dtype=jnp.float32))
        self._learning_rate.persistable = True
        if self._lr_scheduler is not None:
            self._lr_scheduler._bind(self._learning_rate)
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        if grad_clip is not None:
            assert isinstance(grad_clip, ClipGradBase)
        self._accumulators = defaultdict(dict)  # name -> {id(param): Tensor}
        self._acc_inits = {}                    # name -> init scalar
        self._aux = {}

    # -- lr ---------------------------------------------------------------------
    def set_lr(self, value):
        self._learning_rate._value = jnp.asarray(float(value), dtype=jnp.float32)

    def get_lr(self):
        if self._lr_scheduler is not None:
            return float(self._lr_scheduler())
        return float(self._learning_rate._val)

    @property
    def _lr(self):
        """Raw traced lr value (reads through capture hook)."""
        return self._learning_rate._value

    # -- accumulators -----------------------------------------------------------
    # -- multi-precision support (reference adam_op.h MPDType path:
    # fp32 master weights + fp32 accumulators for fp16/bf16 params) --------
    _multi_precision = False  # optimizers with the flag set it in __init__

    def _mp_active(self, p):
        return self._multi_precision and p._val.dtype in (
            jnp.bfloat16.dtype, jnp.float16.dtype)

    def _get_master(self, p):
        accs = self._accumulators["master_weight"]
        mw = accs.get(id(p))
        if mw is None:
            mw = Tensor(unwrap(p._value).astype(jnp.float32))
            mw.persistable = True
            accs[id(p)] = mw
            self._acc_inits["master_weight"] = 0.0
        return mw

    def _get_accumulator(self, name, param, init=0.0, dtype=None, shape=None):
        key = id(param)
        self._acc_inits[name] = init
        acc = self._accumulators[name].get(key)
        if acc is None:
            shp = tuple(shape) if shape is not None else tuple(param._val.shape)
            d = dtype or param._val.dtype
            acc = Tensor(jnp.full(shp, init, dtype=d))
            acc.persistable = True
            self._accumulators[name][key] = acc
        return acc

    # -- main entry points ------------------------------------------------------
    def _collect_params_grads(self):
        params = self._parameter_list
        if params is None:
            raise ValueError(
                "parameters must be passed to the optimizer in eager mode")
        pairs = []
        for p in params:
            if not p.trainable or p.stop_gradient:
                continue
            pairs.append((p, p.grad))
        return pairs

    def _apply_decay(self, params_grads):
        """Regularization folded into grads (fluid/regularizer.py
        append_regularization_ops parity): a per-param regularizer from
        ParamAttr takes precedence over the optimizer-level weight_decay.
        Decoupled decay (AdamW) overrides _apply_update instead."""
        from ..core.selected_rows import SelectedRows
        wd = self._weight_decay
        coeff = 0.0
        if wd is not None:
            coeff = float(wd) if not hasattr(wd, "_coeff") else wd._coeff
        out = []
        for p, g in params_grads:
            if g is None:
                out.append((p, g))
                continue
            if isinstance(g, SelectedRows):
                # reference behavior: L2Decay on sparse grads is skipped
                # (regularizer warns + passes through for SelectedRows)
                out.append((p, g))
                continue
            reg = getattr(p, "regularizer", None)
            if reg is not None:
                g = Tensor(unwrap(g) + reg.grad_term(p._value),
                           stop_gradient=True)
            elif coeff:
                if self._mp_active(p):
                    # fp32 decay against the master: a bf16 decay term can
                    # round away entirely (ulp at |g|=0.1 is ~4e-4)
                    mw = self._get_master(p)
                    g = Tensor(unwrap(g).astype(jnp.float32)
                               + coeff * mw._value, stop_gradient=True)
                else:
                    g = Tensor(unwrap(g) + coeff * p._value,
                               stop_gradient=True)
            out.append((p, g))
        return out

    @autograd.no_grad()
    @jax.named_scope("optimizer")   # clip, decay and every update, in the HLO
    def step(self):
        from ..core.selected_rows import SelectedRows
        pairs = self._collect_params_grads()
        if self._grad_clip is not None:
            # Clip fns are elementwise scalers over arrays. A merged
            # SelectedRows' value block has the same norm as its dense
            # equivalent, so clip the value block through a proxy Tensor and
            # rebuild — the grad STAYS sparse (reference clips SelectedRows
            # via merge, never densifying).
            sparse_slots = {}
            proxied = []
            for i, (p, g) in enumerate(pairs):
                gv = unwrap(g)
                if isinstance(gv, SelectedRows):
                    sr = gv.merge()
                    sparse_slots[i] = sr
                    proxied.append((p, Tensor(sr.value, stop_gradient=True)))
                else:
                    proxied.append((p, g))
            clipped = list(self._grad_clip(proxied))
            for i, sr in sparse_slots.items():
                p, gt = clipped[i]
                clipped[i] = (p, SelectedRows(sr.rows, unwrap(gt),
                                              sr.height))
            pairs = clipped
        pairs = self._apply_decay(pairs)
        for p, g in pairs:
            if g is None:
                continue
            gv = unwrap(g)
            if isinstance(gv, SelectedRows):
                self._apply_sparse_update(p, gv)
            else:
                self._apply_update(p, gv)

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..core.dispatch import get_static_builder
        b = get_static_builder()
        if b is not None:  # static-graph build (optimizer.py minimize:1036)
            b.record_minimize(self, loss)
            return None, None
        loss.backward()
        self.step()
        return None, None

    def _apply_update(self, param, grad):
        raise NotImplementedError

    def _apply_sparse_update(self, param, sr):
        """SelectedRows grad. Default: densify (correct for every rule);
        optimizers with true row-wise kernels (SGD, Adam lazy_mode) override."""
        self._apply_update(param, sr.to_dense())

    def clear_grad(self, set_to_zero=False):
        if self._parameter_list:
            for p in self._parameter_list:
                p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    # -- state dict -------------------------------------------------------------
    def state_dict(self):
        sd = {}
        names = {id(p): (p.name or f"param_{i}")
                 for i, p in enumerate(self._parameter_list or [])}
        for acc_name, by_param in self._accumulators.items():
            for pid, t in by_param.items():
                sd[f"{names.get(pid, pid)}__{acc_name}"] = t
        for k, t in self._aux.items():
            sd[k] = t
        if self._lr_scheduler is not None:
            sd["LR_Scheduler"] = self._lr_scheduler.state_dict()
        return sd

    def set_state_dict(self, state_dict):
        names = {(p.name or f"param_{i}"): p
                 for i, p in enumerate(self._parameter_list or [])}
        for key, val in state_dict.items():
            if key == "LR_Scheduler":
                if self._lr_scheduler is not None:
                    self._lr_scheduler.set_state_dict(val)
                continue
            if "__" in key:
                pname, acc_name = key.rsplit("__", 1)
                p = names.get(pname)
                if p is not None:
                    acc = self._get_accumulator(acc_name, p)
                    acc._value = unwrap(val) if isinstance(val, Tensor) else jnp.asarray(val)
            elif key in self._aux:
                self._aux[key]._value = unwrap(val) if isinstance(val, Tensor) else jnp.asarray(val)

    def _aux_scalar(self, key, init, dtype=jnp.float32):
        t = self._aux.get(key)
        if t is None:
            t = Tensor(jnp.asarray(init, dtype=dtype))
            t.persistable = True
            self._aux[key] = t
        return t
