"""Random MinkUNet weights from the seed, made on the device in one call.

The tree has the layout the served model takes (stem, encoder stages
with down conv and blocks, decoder stages with up conv and blocks, head)
and is made by the benchmark, so the reference may use it.  Conv weights
are uniform in +-1/sqrt(K * cin); layernorm scales are drawn from
[0.5, 1.5] and biases from [-0.2, 0.2], so that the comparison sees
every parameter of the epilogues and not a neutral one.
"""

from __future__ import annotations

import math


def key_for(seed: int):
    """A JAX key from a seed of any size (more than 32 bits included)."""
    import jax
    return jax.random.fold_in(jax.random.key(seed % 2**32),
                              (seed >> 32) % 2**31)


def _shapes(cfg: dict) -> dict:
    """Parameter tree of the configuration with a shape tuple per leaf."""
    def norm(c):
        return {"scale": ("scale", c), "bias": ("bias", c)}

    def block(cin, cout):
        b = {"conv1": (27, cin, cout), "n1": norm(cout),
             "conv2": (27, cout, cout), "n2": norm(cout)}
        if cin != cout:
            b["proj"] = {"w": ("dense", cin, cout)}
        return b

    c = cfg["stem"]
    tree = {"stem": (27, cfg["c_in"], c), "stem_n": norm(c)}
    enc, skip_cs = [], [c]
    for planes in cfg["enc_planes"]:
        blocks = [block(planes, planes)
                  for _ in range(cfg["blocks_per_stage"])]
        enc.append({"down": (8, c, planes), "down_n": norm(planes),
                    "blocks": blocks})
        c = planes
        skip_cs.append(planes)
    dec = []
    n_stages = len(cfg["enc_planes"])
    for i, planes in enumerate(cfg["dec_planes"]):
        blocks, cb = [], planes + skip_cs[n_stages - 1 - i]
        for _ in range(cfg["blocks_per_stage"]):
            blocks.append(block(cb, planes))
            cb = planes
        dec.append({"up": (8, c, planes), "up_n": norm(planes),
                    "blocks": blocks})
        c = planes
    tree["enc"], tree["dec"] = enc, dec
    tree["head"] = {"w": ("dense", c, cfg["n_classes"]),
                    "b": ("bias", cfg["n_classes"])}
    return tree


def make_params(cfg: dict, seed: int, device=None):
    """The configuration's weights in float32, on `device`."""
    import jax
    import jax.numpy as jnp

    spec = _shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple))

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, leaf in zip(keys, leaves):
            if leaf[0] == "scale":
                out.append(jax.random.uniform(k, leaf[1:], jnp.float32,
                                              0.5, 1.5))
            elif leaf[0] == "bias":
                out.append(jax.random.uniform(k, leaf[1:], jnp.float32,
                                              -0.2, 0.2))
            else:
                shape = leaf[1:] if leaf[0] == "dense" else leaf
                fan_in = math.prod(shape[:-1])
                s = 1.0 / math.sqrt(fan_in)
                out.append(jax.random.uniform(k, shape, jnp.float32, -s, s))
        return jax.tree_util.tree_unflatten(treedef, out)

    sharding = None if device is None else \
        jax.sharding.SingleDeviceSharding(device)
    return jax.jit(init, out_shardings=sharding)(key_for(seed))
