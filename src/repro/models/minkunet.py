"""MinkowskiUNet-style sparse conv U-Net (paper's MinkNet(i)/(o) benchmark)
plus the Mini-MinkowskiUNet co-design (paper §5.2.2 / Fig. 16).

Structure: submanifold stem -> N encoder stages (stride-2 down conv +
residual blocks) -> N decoder stages (transposed conv back onto the cached
finer cloud + skip concat + residual blocks) -> linear head.

The network is written against the `PointAccSession` frontend
(`repro.api`): every conv is `session.conv` / `session.conv_transposed`
on a `SparseTensor`, and the tensor's shared `MapContext` owns what used
to be hand-threaded — one `SortedCloud` ranking sort per stride level,
kernel maps shared by every conv at that level, swapped inverse maps for
the decoder found by stride-pair lookup, and per-site temporal-fusion
plans.  Every conv carries its epilogue (layernorm / residual / ReLU /
row-mask) as a `core.sparseconv.Epilogue`, so the executor is
flow-uniform: the XLA flows run epilogues as post-ops while
`flow="pallas_fused"` folds fusable epilogues into the Pallas kernel
flush (paper §4.2.4 fusion extended from FC chains to the conv trunk).
For the fused flow the forward first canonicalises the cloud into
packed-key order — reusing the context's one ranking sort, so the whole
network still costs one `lax.sort` per stride level — and scatters the
head output back to the caller's row order.

`minkunet_apply` / `build_unet_maps` keep their PR-2 signatures as thin
shims over the session API (serving code passes prebuilt level pyramids
through them).
"""

from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro import nn
from repro.api import PointAccSession
from repro.core import fusion as FU
from repro.core import mapping as M
from repro.core import sparseconv as SC
from repro.core.tensor import MapContext, SparseTensor


def conv_w_init(key, k: int, c_in: int, c_out: int, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(k * c_in)
    return jax.random.uniform(key, (k, c_in, c_out), dtype, -scale, scale)


def _block_init(key, c_in: int, c_out: int):
    ks = jax.random.split(key, 4)
    p = {
        "conv1": conv_w_init(ks[0], 27, c_in, c_out),
        "n1": nn.layernorm_init(c_out),
        "conv2": conv_w_init(ks[1], 27, c_out, c_out),
        "n2": nn.layernorm_init(c_out),
    }
    if c_in != c_out:
        p["proj"] = nn.dense_init(ks[2], c_in, c_out, use_bias=False)
    return p


def _norm_epilogue(n_params, mask, residual=None):
    """Epilogue of every trunk conv: layernorm -> (+skip) -> ReLU -> mask."""
    return SC.Epilogue(ln_scale=n_params["scale"], ln_bias=n_params["bias"],
                       relu=True, mask=mask, residual=residual)


def minkunet_init(key, c_in: int = 4, n_classes: int = 13,
                  stem: int = 32,
                  enc_planes: Sequence[int] = (32, 64, 128, 256),
                  dec_planes: Sequence[int] = (256, 128, 96, 96),
                  blocks_per_stage: int = 2):
    n_stages = len(enc_planes)
    keys = iter(jax.random.split(key, 4 + 4 * n_stages * (blocks_per_stage
                                                          + 1)))
    params = {"stem": conv_w_init(next(keys), 27, c_in, stem),
              "stem_n": nn.layernorm_init(stem)}
    c = stem
    enc = []
    for i, planes in enumerate(enc_planes):
        stage = {"down": conv_w_init(next(keys), 8, c, planes),
                 "down_n": nn.layernorm_init(planes),
                 "blocks": []}
        c = planes
        for _ in range(blocks_per_stage):
            stage["blocks"].append(_block_init(next(keys), c, planes))
        enc.append(stage)
    params["enc"] = enc
    dec = []
    skip_cs = [stem] + list(enc_planes[:-1])
    for i, planes in enumerate(dec_planes):
        stage = {"up": conv_w_init(next(keys), 8, c, planes),
                 "up_n": nn.layernorm_init(planes),
                 "blocks": []}
        c_cat = planes + skip_cs[-(i + 1)]
        cb = c_cat
        for _ in range(blocks_per_stage):
            stage["blocks"].append(_block_init(next(keys), cb, planes))
            cb = planes
        dec.append(stage)
        c = planes
    params["dec"] = dec
    params["head"] = nn.dense_init(next(keys), c, n_classes)
    return params


# ---------------------------------------------------------------------------
# session-native forward
# ---------------------------------------------------------------------------

def _block_forward(session: PointAccSession, p, x: SparseTensor):
    """One residual block: two submanifold convs with fused epilogues."""
    h = session.conv(x, p["conv1"],
                     epilogue=_norm_epilogue(p["n1"], x.mask))
    skip = nn.dense(p["proj"], x.feats) if "proj" in p else x.feats
    return session.conv(h, p["conv2"],
                        epilogue=_norm_epilogue(p["n2"], x.mask,
                                                residual=skip))


def minkunet_forward(session: PointAccSession, params,
                     x: SparseTensor) -> jnp.ndarray:
    """Forward pass through the session frontend.

    The session picks the flow/engine/fusion budget; the tensor's
    MapContext accumulates clouds and maps as the convs demand them (one
    ranking sort per stride level).  For `flow="pallas_fused"` on a fresh
    context the cloud is first canonicalised into packed-key order
    (reusing the context's sort) so the streamed kernel's cache-block
    windows stay tight; the head output is scattered back to the caller's
    row order.  A context that already carries maps (e.g. rebuilt from a
    cached level pyramid) is used as-is.
    """
    n_stages = len(params["enc"])
    order = None
    if session.config.flow == "pallas_fused" and not x.context.maps:
        x, order = session.canonicalized(x)

    h = session.conv(x, params["stem"],
                     epilogue=_norm_epilogue(params["stem_n"], x.mask))

    skips = [h]
    for stage in params["enc"]:
        out_mask = session.out_cloud(h, 2).mask
        h = session.conv(h, stage["down"], stride=2,
                         epilogue=_norm_epilogue(stage["down_n"], out_mask))
        for b in stage["blocks"]:
            h = _block_forward(session, b, h)
        skips.append(h)

    for i, stage in enumerate(params["dec"]):
        skip = skips[n_stages - 1 - i]          # target (finer) level
        h = session.conv_transposed(
            h, stage["up"], stride=2,
            epilogue=_norm_epilogue(stage["up_n"], skip.mask))
        h = h.with_feats(jnp.concatenate([h.feats, skip.feats], axis=-1))
        for b in stage["blocks"]:
            h = _block_forward(session, b, h)

    out = nn.dense(params["head"], h.feats) * h.mask[:, None]
    if order is not None:
        out = jnp.zeros_like(out).at[order].set(out)
    return out


# ---------------------------------------------------------------------------
# level-pyramid shims (serving caches pass prebuilt pyramids around)
# ---------------------------------------------------------------------------

def build_unet_maps(pc: M.PointCloud, n_stages: int,
                    engine: str | None = None):
    """Mapping-Unit pass: clouds + kernel maps for every resolution level.

    Returns per-level dicts with the submanifold (k=3) maps, the stride-2
    down maps into the next level, and the level's point cloud — the
    serialisable form of a `MapContext` (see `_context_from_levels` for
    the way back).  Decoder reuses `down` swapped.

    With the packed-key engine (default) each level's cloud is ranked
    exactly ONCE: the level's SortedCloud serves its 27 submanifold
    offsets AND the 8 down-conv offsets, and the downsample hands the next
    level its cloud already sorted — one ranking sort per stride level
    for the entire network, every conv afterwards is key lookups
    (`M.match_table`).
    """
    ctx = MapContext(engine=engine)
    ctx.register_cloud(pc.stride, pc)
    levels = []
    stride = pc.stride
    for i in range(n_stages + 1):
        subm, _ = ctx.conv_maps(3, stride, 1)
        level = {"pc": ctx.point_cloud(stride), "subm": subm}
        if ctx.engine == "v2":
            level["cloud"] = ctx.sorted_cloud(stride)
        if i < n_stages:
            level["down"], _ = ctx.conv_maps(2, stride, 2)
            stride *= 2
        levels.append(level)
    return levels


def _context_from_levels(levels, base_stride: int = 1) -> MapContext:
    """Rebuild a MapContext from a `build_unet_maps` level pyramid.

    Level pyramids that crossed a jit boundary carry array-ified stride
    leaves, so strides are reassigned statically (level i sits at
    base_stride * 2^i — the UNet convention the pyramid was built with).
    """
    engine = "v2" if any("cloud" in lv for lv in levels) else "v1"
    ctx = MapContext(engine=engine)
    stride = base_stride
    for level in levels:
        ctx.clouds[stride] = level.get("cloud", level["pc"])
        ctx.maps[(3, stride, stride)] = level["subm"]
        if "down" in level:
            ctx.maps[(2, stride, 2 * stride)] = level["down"]
        stride *= 2
    return ctx


def minkunet_apply(params, pc: M.PointCloud, feats: jnp.ndarray,
                   flow: str = "fod", levels=None,
                   fused_budget: int | None = None):
    """Deprecated shim over the session API (kept for PR-2 call sites).

    Equivalent to building a `PointAccSession` with (flow, fused_budget)
    and running `minkunet_forward`; pass precomputed `levels` (a
    `build_unet_maps` pyramid, e.g. from a serving cache) to skip map
    building.  New code should hold a session and call
    `minkunet_forward(session, params, session.tensor(...))` directly.
    """
    session = PointAccSession(flow=flow, fused_budget=fused_budget)
    context = _context_from_levels(levels, pc.stride) \
        if levels is not None else None
    x = session.tensor(pc.coords, pc.mask, feats, stride=pc.stride,
                       context=context)
    return minkunet_forward(session, params, x)


def epilogue_dram_bytes(params, levels, fused: bool) -> int:
    """Fig.-20-style DRAM model for the conv epilogues of one forward pass:
    sum `core.fusion.dram_bytes_conv_epilogue` over every conv site.  The
    unfused total counts each conv's pre-activation write + read-back; the
    fused total only the final activation writes (+ residual reads)."""
    n_stages = len(params["enc"])

    def site(n_out, w, residual=False):
        return FU.dram_bytes_conv_epilogue(n_out, w.shape[2],
                                           residual=residual, fused=fused)

    def block(p, cap):
        return site(cap, p["conv1"]) + site(cap, p["conv2"], residual=True)

    total = site(levels[0]["pc"].capacity, params["stem"])
    for i, stage in enumerate(params["enc"]):
        cap = levels[i + 1]["pc"].capacity
        total += site(cap, stage["down"])
        total += sum(block(b, cap) for b in stage["blocks"])
    for i, stage in enumerate(params["dec"]):
        cap = levels[n_stages - 1 - i]["pc"].capacity
        total += site(cap, stage["up"])
        total += sum(block(b, cap) for b in stage["blocks"])
    return total


def halo_spec(params):
    """Receptive-field spec of this UNet for the partition planner.

    `repro.partition.halo` mirrors the network's conv sites backward to
    compute exact per-chunk halos; this names what it must mirror: one
    stem dilation at level 0, two submanifold dilations per residual
    block at every level each stage touches (encoder and decoder), with
    the stride-2 down / transposed convs as the level transitions.
    """
    from repro.partition.halo import HaloSpec
    n_stages = len(params["enc"])
    blocks = len(params["enc"][0]["blocks"]) if n_stages else 0
    return HaloSpec.uniform(n_stages, blocks)


def mini_minkunet_init(key, c_in: int = 4, n_classes: int = 13):
    """The paper's co-designed shallow/narrow MinkowskiUNet (Fig. 16)."""
    return minkunet_init(key, c_in, n_classes, stem=16,
                         enc_planes=(16, 32), dec_planes=(32, 16),
                         blocks_per_stage=1)
