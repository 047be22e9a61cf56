"""Plain MinkUNet forward pass, the reference that decides `correct`.

It imports nothing of the program.  Neighbours come from `bench.count`'s
sorted-key search over each scene's own voxels; the convs are a gather
of the (rows, 27 or 8, cin) neighbour features and one einsum, at the
matmul precision the configuration states (`matmul_precision`:
"default", one bfloat16 pass of float32 operands on the TPU, or
"highest"), layer by layer.  Level 0's rows are padded
to a power of two (at least 256) and level l's to that over 2^(l-1), or
further where a level is fuller, so that the programs depend on the
scene's size class alone and a run finds them all compiled by an earlier
one; padded rows read a zero row and are masked to zero.

The network, as the configuration file states it: a k=3 submanifold stem;
per encoder stage a k=2 stride-2 down conv and `blocks_per_stage`
residual blocks; per decoder stage a k=2 transposed conv onto the finer
level, concatenation with that level's skip, and residual blocks; a
linear head.  Every conv is followed by layernorm (eps `ln_eps`) and
ReLU; a block adds its input (through a bias-free 1x1 projection when
the widths differ) after the second layernorm and before its ReLU.

`dtype` runs the same code in another precision, and `mm_dtype` rounds
every matmul operand to a narrower type (accumulating in `dtype`): these
make the controls that the comparison must refuse (`bench/control.py`).
"""

from __future__ import annotations

import numpy as np

from bench import count as C

MIN_ROWS = 256


def padded(n: int) -> int:
    return max(MIN_ROWS, 1 << max(0, n - 1).bit_length())


def geometry(xyz: np.ndarray, n_stages: int) -> dict:
    """Host-side neighbour tables of one scene, padded per level.

    A missing neighbour, and every padded row, points at row `cap` of its
    source level: the zero row the conv appends."""
    levels = C.pyramid(xyz, n_stages + 1)
    top = padded(len(levels[0]))
    caps = [max(padded(len(lv)), top >> max(0, i - 1))
            for i, lv in enumerate(levels)]
    subm, down, up_parent, up_k, mask = [], [], [], [], []
    for i, lv in enumerate(levels):
        n, cap = len(lv), caps[i]
        t = C.subm_table(lv)
        t = np.where(t == n, cap, t)
        subm.append(_pad_rows(t, cap, cap))
        m = np.zeros(cap, np.float32)
        m[:n] = 1.0
        mask.append(m)
        if i < n_stages:
            coarse = levels[i + 1]
            d = C.down_table(lv, coarse)
            d = np.where(d == n, cap, d)
            down.append(_pad_rows(d, caps[i + 1], cap))
            parent, k = C.up_index(lv, coarse)
            up_parent.append(_pad_rows(parent, cap, caps[i + 1]))
            up_k.append(_pad_rows(k, cap, 0))
    return {"n": [len(lv) for lv in levels], "subm": subm, "down": down,
            "up_parent": up_parent, "up_k": up_k, "mask": mask}


def _pad_rows(a: np.ndarray, rows: int, fill: int) -> np.ndarray:
    out = np.full((rows,) + a.shape[1:], fill, np.int32)
    out[:len(a)] = a
    return out


def _ops():
    """The jitted layer programs (built on first use: importing this
    module touches no device)."""
    import functools

    import jax
    import jax.numpy as jnp

    def mm(a, b, spec, mm_dtype, precision):
        """einsum at `precision`; operands first rounded to mm_dtype if
        given."""
        if mm_dtype is not None:
            a = a.astype(mm_dtype).astype(a.dtype)
            b = b.astype(mm_dtype).astype(b.dtype)
        return jnp.einsum(spec, a, b, precision=PRECISIONS[precision],
                          preferred_element_type=a.dtype)

    def layernorm(y, scale, bias, eps):
        mu = jnp.mean(y, -1, keepdims=True)
        var = jnp.mean(jnp.square(y - mu), -1, keepdims=True)
        return (y - mu) * jax.lax.rsqrt(var + eps) * scale + bias

    static = ("eps", "mm_dtype", "precision")

    @functools.partial(jax.jit, static_argnames=static)
    def conv(x, table, w, scale, bias, mask, residual, eps, mm_dtype,
             precision):
        """Gathered conv + layernorm (+ residual) + ReLU + row mask."""
        xz = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
        y = mm(xz[table], w, "nkc,kcd->nd", mm_dtype, precision)
        y = layernorm(y, scale, bias, eps)
        if residual is not None:
            y = y + residual
        return jax.nn.relu(y) * mask[:, None]

    @functools.partial(jax.jit, static_argnames=static)
    def up(x, parent, k, w, scale, bias, mask, eps, mm_dtype, precision):
        """Transposed k=2 conv: each fine row takes its parent's features
        through the weight of its child offset."""
        xz = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
        g = xz[parent][:, None, :] * jax.nn.one_hot(k, 8, dtype=x.dtype)[
            :, :, None]
        y = mm(g, w, "nkc,kcd->nd", mm_dtype, precision)
        return jax.nn.relu(layernorm(y, scale, bias, eps)) * mask[:, None]

    mm_static = ("mm_dtype", "precision")

    @functools.partial(jax.jit, static_argnames=mm_static)
    def dense(x, w, mm_dtype, precision):
        return mm(x, w, "nc,cd->nd", mm_dtype, precision)

    @functools.partial(jax.jit, static_argnames=mm_static)
    def head(x, w, b, mask, mm_dtype, precision):
        return (mm(x, w, "nc,cd->nd", mm_dtype, precision) + b) * \
            mask[:, None]

    return conv, up, dense, head


_OPS = None
PRECISIONS = {"default": None, "highest": "highest"}


def forward(params, geo: dict, feats: np.ndarray, cfg: dict,
            dtype="float32", mm_dtype=None) -> np.ndarray:
    """Logits (n, n_classes) of one scene, in the row order of `feats`, at
    the configuration's `matmul_precision`.

    `params` is the parameter tree the benchmark made (see
    `bench.weights`); `geo` is `geometry(xyz, n_stages)` of the scene."""
    import jax
    import jax.numpy as jnp

    global _OPS
    if _OPS is None:
        _OPS = _ops()
    conv, up, dense, head = _OPS
    dt = jnp.dtype(dtype)
    eps = float(cfg["ln_eps"])
    p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, dt), params)
    n_stages = len(cfg["enc_planes"])
    caps = [len(m) for m in geo["mask"]]
    mask = [jnp.asarray(m, dt) for m in geo["mask"]]
    subm = [jnp.asarray(t) for t in geo["subm"]]

    mm_kw = {"mm_dtype": mm_dtype, "precision": cfg["matmul_precision"]}
    kw = {"eps": eps, **mm_kw}

    def norm(q):
        return q["scale"], q["bias"]

    def block(b, x, lvl):
        h = conv(x, subm[lvl], b["conv1"], *norm(b["n1"]), mask[lvl], None,
                 **kw)
        skip = dense(x, b["proj"]["w"], **mm_kw) \
            if "proj" in b else x
        return conv(h, subm[lvl], b["conv2"], *norm(b["n2"]), mask[lvl],
                    skip, **kw)

    x = np.zeros((caps[0], feats.shape[1]), np.float32)
    x[:len(feats)] = feats
    h = conv(jnp.asarray(x, dt), subm[0], p["stem"], *norm(p["stem_n"]),
             mask[0], None, **kw)
    skips = [h]
    for i, stage in enumerate(p["enc"]):
        h = conv(h, jnp.asarray(geo["down"][i]), stage["down"],
                 *norm(stage["down_n"]), mask[i + 1], None, **kw)
        for b in stage["blocks"]:
            h = block(b, h, i + 1)
        skips.append(h)
    for i, stage in enumerate(p["dec"]):
        fine = n_stages - 1 - i
        h = up(h, jnp.asarray(geo["up_parent"][fine]),
               jnp.asarray(geo["up_k"][fine]), stage["up"],
               *norm(stage["up_n"]), mask[fine], **kw)
        h = jnp.concatenate([h, skips[fine]], -1)
        for b in stage["blocks"]:
            h = block(b, h, fine)
    out = head(h, p["head"]["w"], p["head"]["b"], mask[0], **mm_kw)
    return np.asarray(out[:len(feats)], np.float32)


def gaps(logits: np.ndarray, classes: np.ndarray) -> np.ndarray:
    """Per row: how far the reference logit of the given class lies below
    the row's best, as a share of the scene's largest |logit|."""
    ref = np.asarray(logits, np.float64)
    scale = max(float(np.abs(ref).max()), 1e-30)
    got = np.take_along_axis(ref, np.asarray(classes)[:, None], -1)[:, 0]
    return (ref.max(-1) - got) / scale
