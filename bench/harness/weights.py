"""Seeded random weights for a configuration, in the layout the served
DiT takes, made on the device in one jitted call.

The distribution follows the published DiT initialisation with every
leaf then perturbed, so that adaLN-Zero's zero gates and zero final
layer do real work: matrices the published model draws as N(0, 0.02^2)
(patch, timestep MLP, class table, qkv, proj, fc1, fc2) get that draw
plus N(0, 0.02^2) more, i.e. N(0, 2 * 0.02^2); the zero-initialised
adaLN, final layer and biases get N(0, 0.02^2); the position table is
the fixed 2-D sin-cos table plus N(0, 0.02^2). Weights come from the
configuration's own ``weights_seed``: they are part of the deployment,
and the run's ``--seed`` draws only the traffic.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

STD = 0.02


def sincos_2d(d: int, grid: int) -> np.ndarray:
    """The 2-D sin-cos position table (MAE / DiT): the first half of each
    row encodes the token's row, the second half its column."""
    def one(dim, pos):
        omega = 1.0 / 10000 ** (np.arange(dim // 2) / (dim / 2.0))
        out = np.einsum("p,f->pf", pos, omega)
        return np.concatenate([np.sin(out), np.cos(out)], axis=1)
    g = np.arange(grid, dtype=np.float64)
    return np.concatenate([one(d // 2, np.repeat(g, grid)),
                           one(d // 2, np.tile(g, grid))], axis=1)


def shapes(c: dict) -> dict:
    """Leaf shapes of the weights tree; (shape, doubled-draw) pairs."""
    d, L = c["hidden_size"], c["depth"]
    f = int(d * c["mlp_ratio"])
    pd = c["patch_size"] ** 2 * c["in_channels"]
    lin = lambda k, n, drawn: {"w": ((k, n), drawn), "b": ((n,), False)}
    stack = lambda k, n, drawn: {"w": ((L, k, n), drawn),
                                 "b": ((L, n), False)}
    return {
        "x_proj": lin(pd, d, True),
        "t_mlp1": lin(256, d, True),
        "t_mlp2": lin(d, d, True),
        "y_embed": {"emb": ((c["num_classes"] + 1, d), True)},
        "blocks": {"qkv": stack(d, 3 * d, True), "proj": stack(d, d, True),
                   "fc1": stack(d, f, True), "fc2": stack(f, d, True),
                   "ada": stack(d, 6 * d, False)},
        "final_ada": lin(d, 2 * d, False),
        "final": lin(d, pd, False),
    }


def make(c: dict):
    """The weights tree for configuration ``c``, on the default device,
    in ``c["dtype"]``."""
    dtype = jnp.dtype(c["dtype"])
    spec = shapes(c)
    leaves, tree = jax.tree.flatten(
        spec, is_leaf=lambda v: isinstance(v, tuple) and len(v) == 2
        and isinstance(v[1], bool))
    grid = c["input_size"] // c["patch_size"]
    pos = sincos_2d(c["hidden_size"], grid).astype(np.float32)

    def build():
        keys = jax.random.split(jax.random.PRNGKey(c["weights_seed"]),
                                len(leaves) + 1)
        out = []
        for (shape, drawn), k in zip(leaves, keys[:-1]):
            std = STD * np.sqrt(2.0) if drawn else STD
            out.append((std * jax.random.normal(k, shape, jnp.float32)
                        ).astype(dtype))
        w = jax.tree.unflatten(tree, out)
        w["pos"] = (jnp.asarray(pos) + STD * jax.random.normal(
            keys[-1], pos.shape, jnp.float32)).astype(dtype)
        return w
    return jax.jit(build)()
