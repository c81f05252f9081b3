"""Weights from ``--seed`` for GMFlow, whose tree has leaves
``benchmark/weights.py`` has no rule for (the ``Linear``s' rank-2 kernels)
and a scope it does not know for an encoder (``backbone``).

The distributions are the public code's (gmflow/backbone.py, transformer.py,
gmflow.py): Kaiming-normal (fan-out) kernels in the backbone;
Xavier-uniform for every ``Linear`` of the Transformer and of the
propagation, whose two biases keep torch's default U(+-1/sqrt(fan_in));
torch's default in the upsampler.  Nothing is inert at this initialisation.
Every other leaf (biases, norm scales and biases a little off 1 and 0) is
``weights.make_variables``'s, from the same keys.

Two groups are then scaled down, so that the softmaxes are not near
one-hot.  As initialised, every LayerNorm adds a unit of variance to the
residual stream and the backbone hands over ~4, so the features that are
matched have a variance of ~16 and the correlation's logits a standard
deviation of ~14 over 3,072 positions (the window attentions' likewise):
which position wins then hangs on the last bit of a feature.  With the
Transformer's LayerNorm scales and biases at ``NORM_SCALE`` of their draw
and the backbone's last 1x1 kernel at ``OUT_SCALE`` of its, the features'
variance is ~1.7 and the logits' standard deviation ~1.5 (PERF.md section
2 has what that did to the readings, and what it did not).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

NORM_SCALE = 0.25      # the Transformer's LayerNorm scales and biases
OUT_SCALE = 0.4        # backbone/conv2, the 1x1 that hands the features over


def make_variables(model, seed: int):
    """``weights.make_variables`` with the rules above in front of its own
    (the rule table is a module-level function that its jitted maker looks
    up when it traces, as ``weights_gma`` has it)."""
    plain = weights._leaf

    def leaf(key, path, shape, fan_in_of):
        name = path[-1]
        if path[0] == "transformer" and path[-2] in ("norm1", "norm2"):
            return NORM_SCALE * plain(key, path, shape, fan_in_of)
        if path == ("backbone", "conv2", "kernel"):
            return (OUT_SCALE * np.sqrt(2.0 / shape[-1])
                    * jax.random.normal(key, shape, jnp.float32))
        if name == "kernel" and len(shape) == 2:
            b = np.sqrt(6.0 / (shape[0] + shape[1]))
            return jax.random.uniform(key, shape, jnp.float32, -b, b)
        if name == "kernel" and path[0] == "backbone":
            kh, kw, _, cout = shape
            return (np.sqrt(2.0 / (kh * kw * cout))
                    * jax.random.normal(key, shape, jnp.float32))
        if name == "bias" and path[0] == "feature_flow_attn":
            b = 1.0 / np.sqrt(fan_in_of[path[:-1]] // shape[0])
            return jax.random.uniform(key, shape, jnp.float32, -b, b)
        return plain(key, path, shape, fan_in_of)

    weights._leaf = leaf
    try:
        return weights.make_variables(model, seed)
    finally:
        weights._leaf = plain
