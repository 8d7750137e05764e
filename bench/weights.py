"""Seeded random weights, made on the device in the program's layout.

Every leaf of the served parameter tree is drawn from ``--seed`` alone:
leaf ``path`` of layer ``l`` is ``normal(fold_in(fold_in(key, crc32(path)),
l)) * scale + offset``, cast to the dtype the program serves it in.  The
program receives the whole tree from one jitted call; the reference draws
the same leaf of one layer again from the seed (``layer_leaf``), so it
takes no array that the program has held.

The scales keep activations of unit size through the stack (fan-in
normal weights, norm gains near 1), so the head's logits have about unit
spread, as a trained model's do.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

#: leaf name -> (std as a function of the per-layer shape, offset)
_FAN0 = lambda s: s[0] ** -0.5
_FAN1 = lambda s: s[1] ** -0.5
SCALES = {
    "embed": (lambda s: 1.0, 0.0),
    "unembed": (_FAN0, 0.0),
    "final_norm": (lambda s: 0.1, 1.0),
    "ln1": (lambda s: 0.1, 1.0),
    "ln2": (lambda s: 0.1, 1.0),
    "wq": (_FAN0, 0.0),
    "wk": (_FAN0, 0.0),
    "wv": (_FAN0, 0.0),
    "wo": (lambda s: (s[0] * s[1]) ** -0.5, 0.0),
    "bq": (lambda s: 0.1, 0.0),
    "bk": (lambda s: 0.1, 0.0),
    "bv": (lambda s: 0.1, 0.0),
    "gate": (_FAN0, 0.0),
    "up": (_FAN0, 0.0),
    "down": (_FAN0, 0.0),
    "router": (_FAN0, 0.0),
    "w_gate": (_FAN1, 0.0),
    "w_up": (_FAN1, 0.0),
    "w_down": (_FAN1, 0.0),
}
#: top-level groups whose leaves carry a leading layer axis
STACKED = ("blocks",)


def base_key(seed: int):
    """A key from any whole seed up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def leaf_specs(tree_spec) -> list:
    """``[(path, ShapeDtypeStruct, stacked)]`` for a parameter tree spec."""
    out = []
    for path, s in jax.tree_util.tree_flatten_with_path(tree_spec)[0]:
        p = _path_str(path)
        if p.split("/")[-1] not in SCALES:
            raise KeyError(f"no weight rule for parameter {p!r}")
        out.append((p, s, p.split("/")[0] in STACKED))
    return out


def _draw(key, path: str, layer, shape, dtype):
    std, offset = SCALES[path.split("/")[-1]]
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(path.encode())
                                              & 0x7FFFFFFF), layer)
    x = jax.random.normal(k, shape, jnp.float32) * std(shape) + offset
    return x.astype(dtype)


def make_params(tree_spec, seed: int, device=None):
    """The whole served tree, drawn on ``device`` in one jitted call (the
    seed's key is an argument, so every seed runs one compiled program)."""
    specs = leaf_specs(tree_spec)
    treedef = jax.tree_util.tree_structure(tree_spec)

    def build(key):
        leaves = []
        for path, s, stacked in specs:
            if stacked:
                leaves.append(jax.vmap(
                    lambda l: _draw(key, path, l, s.shape[1:], s.dtype))(
                        jnp.arange(s.shape[0])))
            else:
                leaves.append(_draw(key, path, 0, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    out = jax.jit(build, out_shardings=None if device is None else
                  jax.sharding.SingleDeviceSharding(device))(base_key(seed))
    return jax.block_until_ready(out)


_draw_one = jax.jit(_draw, static_argnums=(1, 3, 4))


def layer_leaf(seed: int, path: str, layer: int, shape, dtype):
    """One layer's slice of leaf ``path`` (``layer`` 0 for unstacked
    leaves), bit for bit as `make_params` drew it, in ``dtype``."""
    return _draw_one(base_key(seed), path, jnp.int32(layer), tuple(shape),
                     jnp.dtype(dtype))
