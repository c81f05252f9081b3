"""Weights from ``--seed`` for a model with leaves ``benchmark/weights.py``
has no rule for: GMA's ``gamma``.

GMA initialises ``gamma = 0`` (core/gma.py ``Aggregate``), which makes
``gamma * (A v)`` inert: a run that dropped the attention block would still
agree with the reference.  So the benchmark draws ``gamma`` from U(0.5, 1.5)
from the seed (listed under ``assumed`` in the configuration file); every
other leaf is ``weights.make_variables``'s, from the same keys.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import weights


def make_variables(model, seed: int):
    """``weights.make_variables`` with the one extra rule.  The rule table
    there is a module-level function that its jitted maker looks up when
    it traces, so the extra rule stands in front of it for this call."""
    plain = weights._leaf

    def leaf(key, path, shape, fan_in_of):
        if path[-1] == "gamma":
            return jax.random.uniform(key, shape, jnp.float32, 0.5, 1.5)
        return plain(key, path, shape, fan_in_of)

    weights._leaf = leaf
    try:
        return weights.make_variables(model, seed)
    finally:
        weights._leaf = plain
