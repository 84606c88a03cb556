"""The benchmark's weights: drawn on the device from the seed.

The program gives only the layout, the tree of shapes and dtypes that
its ``init_model`` would build (``jax.eval_shape``, nothing computed).
Every value comes from here, in one jitted call, in the dtype served:

* U and V factors (``u``, ``v``): normal with variance 1/k, so that
  W_pq = U diag(s) V has the scale of an orthogonal-basis factorisation;
* Sigma (``s``): normal with the Glorot scale sqrt(2 / (M + N)) x sqrt(k);
* the embedding table (``e``): normal / sqrt(d), times the
  configuration's ``embed_scale``, so that at random init the context,
  and not the last token alone, decides each argmax;
* norm gains (``g``) one, biases (``b``) zero;
* any other floating-point leaf (a router, an untied unembedding, a
  correction bias): at rank 2 and up normal x (its last axis)^-1/2,
  drawn in float32; at rank 1, zero.

Leaf i of the flattened layout draws from ``fold_in(key, i)``.  The
reference reads these same arrays, never the program's.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["seed_key", "make_weights"]


def seed_key(seed: int) -> jax.Array:
    """A JAX key from a non-negative seed of any size."""
    lo, hi = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo)), int(hi))


def _leaf(key, name: str, sds, d_model: int, embed_scale: float):
    shape, dtype = sds.shape, sds.dtype
    if name in ("u", "v"):
        k = shape[-1]
        return jax.random.normal(key, shape, dtype) * jnp.asarray(
            k ** -0.5, dtype)
    if name == "s":
        p, q, k = shape[-3:]
        scale = math.sqrt(2.0 / (p * k + q * k)) * math.sqrt(k)
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(dtype)
    if name == "e":
        return (jax.random.normal(key, shape, jnp.float32)
                * (d_model ** -0.5 * embed_scale)).astype(dtype)
    if name == "g":
        return jnp.ones(shape, dtype)
    if not jnp.issubdtype(dtype, jnp.floating):
        raise ValueError(f"no rule for a {dtype} weight leaf named {name!r}")
    if name == "b" or len(shape) < 2:
        return jnp.zeros(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32)
            * shape[-1] ** -0.5).astype(dtype)


def make_weights(key: jax.Array, layout, d_model: int, embed_scale: float):
    """Fill ``layout`` (a tree of ShapeDtypeStruct) from ``key``."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(layout)

    def fill(key):
        return treedef.unflatten([
            _leaf(jax.random.fold_in(key, i), path[-1].key, sds, d_model,
                  embed_scale)
            for i, (path, sds) in enumerate(paths)])

    return jax.jit(fill)(key)
