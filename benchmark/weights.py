"""Weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights itself, so that the program and the plain
reference start from the same tree without either making it for the other.
Only the tree's *names and shapes* come from the program (``eval_shape`` of
its ``init``: nothing is computed); the values follow the distributions the
paper's code initialises with: Kaiming-normal (fan-out) kernels in the two
encoders, PyTorch's default uniform(+-1/sqrt(fan_in)) everywhere else and
for every bias.  Norm scales, biases and running statistics are drawn a
little off 1 and 0, so that a path that drops them does not go unseen.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax.traverse_util import flatten_dict, unflatten_dict


def tree_shapes(model, small_hw=(64, 96)):
    z = jnp.zeros((1,) + tuple(small_hw) + (3,), jnp.float32)
    shapes = jax.eval_shape(
        lambda k: model.init({"params": k, "dropout": k}, z, z, iters=1),
        jax.random.PRNGKey(0))
    return {c: flatten_dict(dict(t)) for c, t in dict(shapes).items()}


def _leaf(key, path, shape, fan_in_of):
    name = path[-1]
    if name == "kernel":
        kh, kw, cin, cout = shape
        if path[0] in ("fnet", "cnet"):
            std = np.sqrt(2.0 / (kh * kw * cout))
            return std * jax.random.normal(key, shape, jnp.float32)
        b = 1.0 / np.sqrt(kh * kw * cin)
        return jax.random.uniform(key, shape, jnp.float32, -b, b)
    if name == "bias" and path[:-1] in fan_in_of:
        b = 1.0 / np.sqrt(fan_in_of[path[:-1]])
        return jax.random.uniform(key, shape, jnp.float32, -b, b)
    if name in ("scale", "var"):
        return 1.0 + 0.2 * jax.random.uniform(key, shape, jnp.float32, -1, 1)
    if name in ("bias", "mean"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    raise ValueError(f"no rule for leaf {'/'.join(path)} {shape}")


def make_variables(model, seed: int):
    """{'params': ..., 'batch_stats': ...} float32 on the default device."""
    shapes = tree_shapes(model)
    fan_in = {p[:-1]: int(np.prod(s.shape[:3]))
              for p, s in shapes["params"].items() if p[-1] == "kernel"}

    @jax.jit
    def make(key):
        out = {}
        for ci, col in enumerate(sorted(shapes)):
            flat = {}
            for li, path in enumerate(sorted(shapes[col])):
                k = jax.random.fold_in(jax.random.fold_in(key, ci), li)
                flat[path] = _leaf(k, path, shapes[col][path].shape, fan_in)
            out[col] = unflatten_dict(flat)
        return out

    # the driver's seeds pass 2**31: fold the high bits in separately
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    out = make(key)
    out.setdefault("batch_stats", {})
    return out
