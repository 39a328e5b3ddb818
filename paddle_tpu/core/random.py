"""Global RNG.

Reference parity: paddle.seed / fluid Generator (paddle/fluid/framework/generator.cc).
TPU-native redesign: the generator state is a JAX PRNG key held inside a Tensor,
so `to_static` functionalization captures it as mutable state — every jitted
step consumes and writes back a fresh key (dropout differs per step inside one
compiled computation), exactly like the reference's per-device Generator but
functional.
"""
from __future__ import annotations

import jax

from .tensor import Tensor, _TraceHooks

__all__ = ["seed", "next_key", "get_state", "set_state", "Generator", "default_generator"]


class Generator:
    def __init__(self, seed_: int = 0):
        # the key Tensor is built on first use: building it here would make
        # `import paddle_tpu` initialise a JAX backend (default_generator
        # below), and a process that has done that holds the chip
        self._seed = int(seed_)
        self._key_tensor = None

    @property
    def _key(self):
        t = self._key_tensor
        if t is None:
            # pre-existing state, not a trace-local temporary: a first use
            # inside a to_static discovery pass must not report it as created
            prev, _TraceHooks.on_create = _TraceHooks.on_create, None
            try:
                t = Tensor(jax.random.key_data(jax.random.PRNGKey(self._seed)),
                           stop_gradient=True)
            finally:
                _TraceHooks.on_create = prev
            t.persistable = True
            t.name = "generator_key"
            self._key_tensor = t
        return t

    def manual_seed(self, seed_: int):
        self._key._value = jax.random.key_data(jax.random.PRNGKey(int(seed_)))
        return self

    def next_key(self):
        """Split the state; returns a raw jax PRNG key for one sampling op."""
        key = jax.random.wrap_key_data(self._key._value)
        new_key, sub = jax.random.split(key)
        self._key._value = jax.random.key_data(new_key)
        return sub

    def next_key_data(self):
        """Split the state; returns the subkey as raw key DATA (uint32
        array) suitable to pass as an op input — prims re-wrap it with
        jax.random.wrap_key_data. Under static-graph build this records a
        generator-split node instead, so each Executor replay draws a fresh
        key (reference: dropout's seed/generator var in static programs)."""
        from .dispatch import get_static_builder
        b = get_static_builder()
        if b is not None:
            return b.record_rng(self)
        return jax.random.key_data(self.next_key())

    def get_state(self):
        return Tensor(self._key._value, stop_gradient=True)

    def set_state(self, state):
        self._key._value = state._value if isinstance(state, Tensor) else state


default_generator = Generator(0)


def seed(s: int):
    """paddle.seed parity."""
    default_generator.manual_seed(s)
    return default_generator


def next_key():
    return default_generator.next_key()


def next_key_data():
    return default_generator.next_key_data()


def get_state():
    return default_generator.get_state()


def set_state(state):
    default_generator.set_state(state)
