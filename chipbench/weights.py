"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program: the same arrays go to
the system under test and to the plain reference.  A spec is ``(kind,
shape)``; fan-in is the product of all axes but the first.  Each array is
drawn by its kind (see a reference's ``param_specs``):

- ``conv``: normal, variance 2 / fan-in (He et al., arXiv:1502.01852);
- ``dense``: normal, variance 1 / fan-in; ``bias``: normal, deviation 0.01;
- ``embedding`` (rows, width): normal, variance 1 / width: a row's norm
  is about 1 whatever the width;
- ``gamma``: 1 + 0.1 normal; ``beta``: 0.1 normal, so that no two leaves
  and no two channels are alike and a mixed-up leaf shows;
- ``gamma_last`` (the batch normalisation that closes a residual branch):
  ``LAST_GAMMA`` times a ``gamma``.  A small last scale keeps the
  freshly initialised net close to the identity on its shortcuts (Goyal
  et al., arXiv:1706.02677, section 5.1), where its first steps are well
  conditioned; with 1 the first backward pass of ResNet-50 amplifies
  rounding until bf16 and float32 decorrelate (PERF.md, PR 21);
- ``running_mean``: 0.1 normal; ``running_var``: 1 + 0.1 normal: batch
  normalisation's running statistics, which the forward pass moves and
  no gradient reaches (``MOVED_BY_FORWARD``).
"""
import math

import jax
import jax.numpy as jnp

LAST_GAMMA = 0.25
#: kinds that the optimizer does not touch
MOVED_BY_FORWARD = ("running_mean", "running_var")


def _draw(kind, shape, key):
    normal = jax.random.normal(key, shape, jnp.float32)
    fan_in = math.prod(shape[1:])
    if kind == "conv":
        return normal * math.sqrt(2.0 / fan_in)
    if kind == "dense":
        return normal * math.sqrt(1.0 / fan_in)
    if kind == "embedding":
        return normal * math.sqrt(1.0 / shape[-1])
    if kind == "bias":
        return 0.01 * normal
    if kind in ("gamma", "running_var"):
        return 1.0 + 0.1 * normal
    if kind == "gamma_last":
        return LAST_GAMMA * (1.0 + 0.1 * normal)
    if kind in ("beta", "running_mean"):
        return 0.1 * normal
    raise ValueError(f"unknown parameter kind {kind!r}")


def seed_key(seed):
    """A key from any whole number, also one beyond 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make(specs, seed):
    """The list of float32 arrays for ``specs``."""
    specs = tuple((kind, tuple(shape)) for kind, shape in specs)

    @jax.jit
    def draw_all(key):
        return [_draw(kind, shape, jax.random.fold_in(key, i))
                for i, (kind, shape) in enumerate(specs)]

    return draw_all(seed_key(seed))
