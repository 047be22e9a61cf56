"""Fetch-on-Demand sparse convolution kernels (PointAcc MMU+MXU, §4.2/§4.3).

TPU adaptation of the paper's dataflow:

  * output-stationary: the (out_tile, Cout) accumulator lives in VMEM scratch
    across all K kernel offsets — partial sums NEVER touch HBM (the paper's
    'eliminate the off-chip scatter of partial sums').
  * weight-stationary inner steps: the kernel-offset weights are VMEM
    resident while feature tiles stream past them (paper §4.2.2).
  * scatter-free: maps are pre-inverted per offset into `inv_idx[k, j] = i`
    (input row feeding output j under offset k, -1 if none).  Each output row
    has at most one contribution per offset (kernel-mapping is 1:1 per
    offset for coordinate-set clouds), so the MXU 'only accesses features of
    one output point in one cycle' (paper §4.3) and no scatter circuit/op is
    needed.
  * fetch-on-demand: input rows are gathered inside the kernel from the
    VMEM-resident feature block immediately before the matmul — the gathered
    matrix is never materialised in HBM (the paper's 3x DRAM saving,
    Fig. 11c).  The gather is one dynamic single-row slice per output row
    and offset, addressed by the tile's slice of the inverse table held in
    SMEM (`_gather_rows`): Mosaic lowers no vector gather across rows, and
    a row copy costs the same whatever the size of the resident block.

Two kernels:

  * `spconv_fod_pallas` — the original realisation: grid (out, cin, K) with
    the whole (N, cin_tile) feature array resident per step.  Kept as the
    `flow="pallas"` baseline and for cross-checking.
  * `spconv_fod_fused_pallas` — the temporal-fusion realisation (§4.2.4):
      - streamed feature tiles: the feature array is cut into `feat_tile`
        row windows (the paper's configurable cache blocks).  A scalar-
        prefetched per-out-tile window map drives the BlockSpec index_map,
        so only the windows an output tile actually references are fetched
        (revisited clamp indices cost no new DMA) and clouds larger than
        VMEM stream with double buffering instead of failing.
      - the K-offset loop runs *inside* the kernel body, so each feature
        window moves HBM->VMEM once per output tile, not once per offset —
        a K-fold cut in feature traffic over the baseline kernel.
      - fused epilogue: the flush applies bias / layernorm / residual-add
        (from a VMEM-resident skip tile) / ReLU / row-mask before the single
        output write, so a conv+norm+activation block writes no
        pre-activation intermediate to HBM.

Window maps rely on no ordering property for correctness — every referenced
window is visited and rows are masked to their window — but when features
are stored in packed-key order (core.mapping.SortedCloud) the inverse
tables are monotone per offset, the per-tile window ranges collapse, and
the sweep touches a near-minimal set of blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.fusion import VMEM_LIMIT_BYTES

LN_EPS = 1e-6  # must match repro.nn.layernorm
# rows gathered per loop iteration (Mosaic unrolls fori_loop only fully)
ROW_UNROLL = 8


def _gather_rows(inv_ref, k, feat_ref, rows_ref, base=0):
    """rows_ref[j] <- feat_ref[inv_ref[k, j] - base] for each row j of the
    output tile whose source row lies in the resident feature block, and
    zero for the rest (no map, or a row of another window)."""
    n_rows = feat_ref.shape[0]

    def body(g, carry):
        for u in range(ROW_UNROLL):
            j = g * ROW_UNROLL + u
            src = inv_ref[k, j]
            loc = src - base
            ok = (src >= 0) & (loc >= 0) & (loc < n_rows)
            row = feat_ref[pl.ds(jnp.clip(loc, 0, n_rows - 1), 1), :]
            rows_ref[pl.ds(j, 1), :] = jnp.where(ok, row, 0).astype(
                rows_ref.dtype)
        return carry

    lax.fori_loop(0, rows_ref.shape[0] // ROW_UNROLL, body, 0)


def _check_out_tile(out_tile: int, m: int, where: str):
    if m % out_tile != 0:
        raise ValueError(
            f"output rows m={m} not a multiple of out_tile={out_tile}; pad "
            f"the inverse table ({where} does)")
    if out_tile % ROW_UNROLL != 0:
        raise ValueError(
            f"out_tile={out_tile} must be a multiple of {ROW_UNROLL}")


def _kernel(inv_ref, feat_ref, w_ref, out_ref, acc_ref, rows_ref, *, n_k,
            n_cin):
    ci = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((k == 0) & (ci == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _gather_rows(inv_ref, k, feat_ref, rows_ref)
    acc_ref[...] += jnp.dot(rows_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when((k == n_k - 1) & (ci == n_cin - 1))
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def spconv_fod_pallas(features: jnp.ndarray, inv_idx: jnp.ndarray,
                      weights: jnp.ndarray, *, out_tile: int = 128,
                      cin_tile: int | None = None,
                      interpret: bool = False) -> jnp.ndarray:
    """features (N, Cin), inv_idx (K, M) int32 (-1 = no map),
    weights (K, Cin, Cout) -> (M, Cout).

    M and N must be multiples of the tile sizes (the ops.py wrapper pads
    both M and Cin).
    """
    n, cin = features.shape
    k, m = inv_idx.shape
    cout = weights.shape[-1]
    cin_tile = cin_tile or cin
    if cin % cin_tile != 0:
        raise ValueError(
            f"cin={cin} is not a multiple of cin_tile={cin_tile}; pad the "
            "channel dim (ops.sparse_conv_fod does) or pick a divisor")
    _check_out_tile(out_tile, m, "ops.sparse_conv_fod")
    n_cin = cin // cin_tile

    grid = (m // out_tile, n_cin, k)

    return pl.pallas_call(
        functools.partial(_kernel, n_k=k, n_cin=n_cin),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, out_tile), lambda o, ci, kk: (0, o),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((n, cin_tile), lambda o, ci, kk: (0, ci)),
            pl.BlockSpec((1, cin_tile, cout),
                         lambda o, ci, kk: (kk, ci, 0)),
        ],
        out_specs=pl.BlockSpec((out_tile, cout), lambda o, ci, kk: (o, 0)),
        out_shape=jax.ShapeDtypeStruct((m, cout), features.dtype),
        scratch_shapes=[pltpu.VMEM((out_tile, cout), jnp.float32),
                        pltpu.VMEM((out_tile, cin_tile), features.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="spconv_fetch_on_demand",
    )(inv_idx, features, weights)


# ---------------------------------------------------------------------------
# fused epilogue + streamed feature tiles
# ---------------------------------------------------------------------------

def _fused_kernel(wmap_ref, nwin_ref, inv_ref, feat_ref, w_ref, *rest,
                  n_k, n_cin, n_win, feat_tile, has_bias, has_ln, has_res,
                  has_mask, relu):
    it = iter(rest)
    bias_ref = next(it) if has_bias else None
    ln_scale_ref = next(it) if has_ln else None
    ln_bias_ref = next(it) if has_ln else None
    res_ref = next(it) if has_res else None
    mask_ref = next(it) if has_mask else None
    out_ref = next(it)
    acc_ref = next(it)
    rows_ref = next(it)

    o = pl.program_id(0)
    ci = pl.program_id(1)
    wi = pl.program_id(2)

    @pl.when((ci == 0) & (wi == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Only the first nwin[o] steps present fresh windows; the remaining
    # sweep steps revisit the last block (clamped index map -> no new DMA)
    # and are skipped so no row is counted twice.
    @pl.when(wi < nwin_ref[o])
    def _compute():
        base = wmap_ref[o * n_win + wi] * feat_tile

        def offset(k, carry):
            _gather_rows(inv_ref, k, feat_ref, rows_ref, base)
            acc_ref[...] += jnp.dot(rows_ref[...], w_ref[k],
                                    preferred_element_type=jnp.float32)
            return carry

        lax.fori_loop(0, n_k, offset, 0)

    @pl.when((ci == n_cin - 1) & (wi == n_win - 1))
    def _flush():
        r = acc_ref[...]                                  # f32 (T, Cout)
        if has_bias:
            r = r + bias_ref[...]                         # (1, Cout)
        if has_ln:
            mu = jnp.mean(r, axis=1, keepdims=True)
            var = jnp.mean(jnp.square(r - mu), axis=1, keepdims=True)
            r = (r - mu) * jax.lax.rsqrt(var + LN_EPS)
            r = r * ln_scale_ref[...] + ln_bias_ref[...]
        if has_res:
            r = r + res_ref[...].astype(jnp.float32)
        if relu:
            r = jnp.maximum(r, 0.0)
        if has_mask:
            r = r * mask_ref[...].astype(jnp.float32)     # (T, 1)
        out_ref[...] = r.astype(out_ref.dtype)


def spconv_fod_fused_pallas(features: jnp.ndarray, inv_idx: jnp.ndarray,
                            weights: jnp.ndarray,
                            wmap: jnp.ndarray, nwin: jnp.ndarray, *,
                            bias: jnp.ndarray | None = None,
                            ln_scale: jnp.ndarray | None = None,
                            ln_bias: jnp.ndarray | None = None,
                            residual: jnp.ndarray | None = None,
                            mask: jnp.ndarray | None = None,
                            relu: bool = False,
                            feat_tile: int, out_tile: int = 128,
                            cin_tile: int | None = None,
                            interpret: bool = False) -> jnp.ndarray:
    """Streamed + fused FoD conv.  features (N, Cin), inv_idx (K, M),
    weights (K, Cin, Cout) -> (M, Cout).

    wmap (M/out_tile, N/feat_tile) int32 and nwin (M/out_tile,) int32 are
    the window schedule: out tile o visits feature row blocks
    wmap[o, 0..nwin[o]-1] (ops.py derives them from the inverse table).
    Both are scalar-prefetched, wmap flattened to 1-D (SMEM pads the
    minor dim of a 2-D array).  Epilogue (all optional, applied in this order at flush):
    +bias (1, Cout) -> layernorm (ln_scale/ln_bias (1, Cout)) ->
    +residual (M, Cout) -> ReLU -> *mask (M, 1).
    """
    n, cin = features.shape
    k, m = inv_idx.shape
    cout = weights.shape[-1]
    cin_tile = cin_tile or cin
    if cin % cin_tile != 0:
        raise ValueError(
            f"cin={cin} is not a multiple of cin_tile={cin_tile}; pad the "
            "channel dim (ops.sparse_conv_fused does) or pick a divisor")
    _check_out_tile(out_tile, m, "ops.sparse_conv_fused")
    if n % feat_tile != 0:
        raise ValueError(
            f"feature rows n={n} not a multiple of feat_tile={feat_tile}; "
            "pad the features (ops.sparse_conv_fused does)")
    if (ln_scale is None) != (ln_bias is None):
        raise ValueError("ln_scale and ln_bias must be passed together")
    n_cin = cin // cin_tile
    n_win = n // feat_tile
    tiles = m // out_tile
    if wmap.shape != (tiles, n_win) or nwin.shape != (tiles,):
        raise ValueError(
            f"window schedule shapes {wmap.shape}/{nwin.shape} do not match "
            f"grid ({tiles}, {n_win})")

    has_bias = bias is not None
    has_ln = ln_scale is not None
    has_res = residual is not None
    has_mask = mask is not None

    in_specs = [
        pl.BlockSpec((k, out_tile), lambda o, ci, wi, wm, nw: (0, o),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((feat_tile, cin_tile),
                     lambda o, ci, wi, wm, nw: (wm[o * n_win + wi], ci)),
        pl.BlockSpec((k, cin_tile, cout),
                     lambda o, ci, wi, wm, nw: (0, ci, 0)),
    ]
    operands = [inv_idx, features, weights]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, cout),
                                     lambda o, ci, wi, wm, nw: (0, 0)))
        operands.append(bias.reshape(1, cout))
    if has_ln:
        for p in (ln_scale, ln_bias):
            in_specs.append(pl.BlockSpec((1, cout),
                                         lambda o, ci, wi, wm, nw: (0, 0)))
            operands.append(p.reshape(1, cout))
    if has_res:
        in_specs.append(pl.BlockSpec((out_tile, cout),
                                     lambda o, ci, wi, wm, nw: (o, 0)))
        operands.append(residual)
    if has_mask:
        in_specs.append(pl.BlockSpec((out_tile, 1),
                                     lambda o, ci, wi, wm, nw: (o, 0)))
        operands.append(mask.reshape(m, 1).astype(features.dtype))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(tiles, n_cin, n_win),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((out_tile, cout),
                               lambda o, ci, wi, wm, nw: (o, 0)),
        scratch_shapes=[pltpu.VMEM((out_tile, cout), jnp.float32),
                        pltpu.VMEM((out_tile, cin_tile), features.dtype)],
    )
    return pl.pallas_call(
        functools.partial(_fused_kernel, n_k=k, n_cin=n_cin, n_win=n_win,
                          feat_tile=feat_tile, has_bias=has_bias,
                          has_ln=has_ln, has_res=has_res, has_mask=has_mask,
                          relu=relu),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, cout), features.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="spconv_fod_fused",
    )(wmap.reshape(-1), nwin, *operands)
