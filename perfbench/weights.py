"""Seeded weights, made by the benchmark and not by the program.

One generator, two readers. The program gets the whole stack in its own
parameter layout (the family's ``program``: one jitted call, on the device,
in the served dtype, born sharded). The plain reference asks for one layer
at a time (``Weights.layer``) and gets the same numbers again from the same
keys, so no second copy of the model ever sits on the device. What a layer
holds is its family's (``perfbench/families/``); the generators and the keys
are every family's.

Norm weights are 1 + 0.1 * N(0, 1), not ones: a forward pass that left a
norm's weight out would otherwise agree with the reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def randw(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(fan_in ** -0.5,
                                                              dtype)


def norm_weight(key, shape):
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def keys(seed: int, n_layers: int):
    """The key of the weights outside the layers, and one key a layer."""
    root = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return (jax.random.fold_in(root, 1_000_003),
            jax.vmap(lambda i: jax.random.fold_in(root, i))(
                jnp.arange(n_layers)))


class Weights:
    """The reference's view: one layer, or the embedding and head, on call."""

    def __init__(self, family, sizes, seed: int, device=None):
        self.family, self.sizes = family, sizes
        # A jitted call runs where its operands live: the keys pin the
        # reference to one device.
        self._g = None
        self._gkey, self._lkeys = jax.device_put(
            keys(seed, sizes.n_layers), device or jax.devices()[0])

    def layer(self, i: int):
        return self.family.layer_weights(self.sizes, self._lkeys[i], i)

    def globals_(self):
        if self._g is None:
            self._g = self.family.global_weights(self.sizes, self._gkey)
        return self._g
