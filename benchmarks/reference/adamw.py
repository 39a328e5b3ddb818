"""AdamW (Loshchilov & Hutter 2019, algorithm 2) in plain float32.

Decoupled decay on every leaf, bias-corrected moments, no schedule. Shares
no code with paddle_tpu. The moments live on the host between steps and
visit the device one leaf at a time: the reference runs on the chip the
program is measured on, and its peak memory has to stay under the
program's.
"""
import jax
import jax.numpy as jnp
import numpy as np


def init(params):
    return {"m": {k: None for k in params}, "v": {k: None for k in params},
            "t": 0}


def update(params, grads, state, lr, beta1=0.9, beta2=0.999, eps=1e-8,
           weight_decay=0.01):
    t = state["t"] + 1

    @jax.jit
    def leaf(p, g, m, v):
        m = beta1 * m + (1.0 - beta1) * g
        v = beta2 * v + (1.0 - beta2) * g * g
        m_hat = m / (1.0 - beta1 ** t)
        v_hat = v / (1.0 - beta2 ** t)
        p = p * (1.0 - lr * weight_decay) - lr * m_hat / (jnp.sqrt(v_hat) + eps)
        return p, m, v

    new_p, new_m, new_v = {}, {}, {}
    for k in params:
        m, v = state["m"][k], state["v"][k]
        if m is None:
            m = v = jnp.zeros_like(params[k])
        new_p[k], m, v = leaf(params[k], grads[k], jnp.asarray(m), jnp.asarray(v))
        new_m[k], new_v[k] = np.asarray(m), np.asarray(v)
    return new_p, {"m": new_m, "v": new_v, "t": t}
