"""Random weights made on the device from the seed, in one jitted call.

The fill runs over the shapes of the model's parameter tree (as
``jax.eval_shape`` of its init gives them) and draws every leaf at the
scale the init uses, by the leaf's name. The same seed gives the same
values to the program (in its serving dtype) and to the reference (the
same values, widened to float32), so the reference needs nothing the
program made.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

# leaf name -> how it is drawn:
#   ("ones",) / ("zeros",)
#   ("normal", s)       normal with standard deviation s
#   ("fan_in", n, r)    normal at 1/sqrt of the product of the first n
#                       dimensions of the leaf's own r-dimensional weight;
#                       the dimensions before those r stack layers and
#                       experts ([L, E, d, f] draws at 1/sqrt(d))
#   ("s4d_real",)       log(1 .. N) along the last (state) axis: Mamba's A
#   ("dt_bias", lo, hi) softplus^-1(exp(U[log lo, log hi])): Mamba's dt
#                       bias, so that softplus(bias) lies in [lo, hi]
RULES = {
    "scale": ("ones",), "q_norm": ("ones",), "k_norm": ("ones",),
    "bq": ("zeros",), "bk": ("zeros",), "bv": ("zeros",),
    "embed": ("normal", 0.02),
    "lm_head": ("fan_in", 1, 2),
    "wq": ("fan_in", 1, 3), "wk": ("fan_in", 1, 3), "wv": ("fan_in", 1, 3),
    "wo": ("fan_in", 2, 3),
    "w_gate": ("fan_in", 1, 2), "w_up": ("fan_in", 1, 2),
    "w_down": ("fan_in", 1, 2),
    "router": ("fan_in", 1, 2),
    "in_proj": ("fan_in", 1, 2), "x_proj": ("fan_in", 1, 2),
    "dt_proj_w": ("fan_in", 1, 2), "out_proj": ("fan_in", 1, 2),
    "conv_w": ("fan_in", 1, 2),
    "conv_b": ("zeros",), "D": ("ones",),
    "A_log": ("s4d_real",),
    "dt_proj_b": ("dt_bias", 1e-3, 1e-1),
}


def _names(path) -> list[str]:
    return [str(getattr(k, "key", getattr(k, "name", k))) for k in path]


def root_key(seed: int):
    """A key for any whole-number seed, also one wider than 32 bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def rule_of(names: list[str]):
    rule = RULES.get(names[-1])
    if rule is None:
        raise KeyError(f"no weight rule for leaf {'/'.join(names)}")
    return rule


def _leaf(key, names, shape, dtype):
    rule = rule_of(names)
    if rule[0] == "ones":
        return jnp.ones(shape, dtype)
    if rule[0] == "zeros":
        return jnp.zeros(shape, dtype)
    if rule[0] == "s4d_real":
        # on the host: the same float32 values on every device
        a = np.log(np.arange(1, shape[-1] + 1, dtype=np.float32))
        return jnp.broadcast_to(jnp.asarray(a), shape).astype(dtype)
    k = jax.random.fold_in(key, zlib.crc32("/".join(names).encode()))
    if rule[0] == "dt_bias":
        u = jax.random.uniform(k, shape, jnp.float32, minval=math.log(rule[1]),
                               maxval=math.log(rule[2]))
        return jnp.log(jnp.expm1(jnp.exp(u))).astype(dtype)
    if rule[0] == "normal":
        scale = rule[1]
    else:
        lead = len(shape) - rule[2]
        scale = 1.0 / math.sqrt(math.prod(shape[lead:lead + rule[1]]))
    return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)


def fill(shapes, seed: int, dtype=jnp.bfloat16):
    """Arrays for every leaf of ``shapes`` (a tree of ShapeDtypeStruct),
    made in one jitted call on the default device."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    specs = [(_names(p), tuple(s.shape)) for p, s in leaves]

    @jax.jit
    def make(key):
        return [_leaf(key, n, shp, dtype) for n, shp in specs]

    return jax.tree_util.tree_unflatten(treedef, make(root_key(seed)))
