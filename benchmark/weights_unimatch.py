"""Weights from ``--seed`` for UniMatch's flow model: ``weights_gmflow``'s
rules (Kaiming-normal fan-out kernels in the backbone, Xavier-uniform
``Linear``s, torch's defaults elsewhere, the Transformer's LayerNorm scales
and biases at ``weights_gmflow.NORM_SCALE`` of a draw a little off 1 and 0,
the backbone's 1x1 projection at ``weights_gmflow.OUT_SCALE`` of its
Kaiming draw), and in front of them one rule of its own: the trident's 3x3,
which follows the projection and as initialised doubles the features'
variance, at ``TRIDENT_SCALE`` of its Kaiming draw.  The features both
scales match then have the variance ``gmflow_base``'s have (0.66 against
0.64 at 128x192), so that no softmax is near one-hot at either scale (PERF.md
section 2).  The refinement (``refine_proj``, ``refine``) keeps torch's
defaults: nothing is inert at this initialisation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights, weights_gmflow

TRIDENT_SCALE = 0.7    # backbone/trident_conv, the 3x3 both scales share
TRIDENT = ("backbone", "trident_conv", "kernel")


def make_variables(model, seed: int):
    """``weights_gmflow.make_variables`` with the trident's rule in front of
    the rules it installs (the rule table is a module-level function that
    the jitted maker looks up when it traces)."""
    real_make = weights.make_variables

    def make(model, seed):
        gmflow_leaf = weights._leaf

        def leaf(key, path, shape, fan_in_of):
            if path == TRIDENT:
                kh, kw, _, cout = shape
                return (TRIDENT_SCALE * np.sqrt(2.0 / (kh * kw * cout))
                        * jax.random.normal(key, shape, jnp.float32))
            return gmflow_leaf(key, path, shape, fan_in_of)

        weights._leaf = leaf
        try:
            return real_make(model, seed)
        finally:
            weights._leaf = gmflow_leaf

    weights.make_variables = make
    try:
        return weights_gmflow.make_variables(model, seed)
    finally:
        weights.make_variables = real_make
