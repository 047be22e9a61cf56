"""jit'd public wrappers: KernelMaps -> inverse table -> Pallas call.

Two entry points mirror the two kernels in spconv.py:

  * `sparse_conv_fod`   — baseline whole-array-resident kernel
    (`flow="pallas"`); channels past `BASE_CIN_TILE` are tiled so the
    resident (N, cin_tile) column fits VMEM at bucket-size clouds.
  * `sparse_conv_fused` — streamed feature tiles + fused epilogue
    (`flow="pallas_fused"`): derives the scalar-prefetched window schedule
    from the inverse table, pads rows/channels to the tile grid, and folds
    the `core.sparseconv.Epilogue` into the kernel flush.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.mapping import KernelMaps
from repro.core.sparseconv import Epilogue
from repro.kernels.spconv.spconv import (spconv_fod_fused_pallas,
                                         spconv_fod_pallas)
from repro.kernels.spconv.ref import spconv_fod_ref


def invert_maps(maps: KernelMaps, out_cap: int) -> jnp.ndarray:
    """(K, cap) map lists -> (K, out_cap) inverse table inv[k, j] = i.

    The v2 packed-key engine emits the inverse table directly from its
    binary-search hit positions (KernelMaps.inv) — that path is a no-op
    here, and since PR 2 it covers swapped maps too: strided v2 maps carry
    the transposed table (KernelMaps.inv_t), which `swap()` promotes to
    `inv`, so decoder transposed convs stay scatter-free.  Only v1 maps
    (and explicitly capped v2 maps, whose tables are dropped) fall back to
    the scatter: kernel mapping is 1:1 per offset (both clouds are
    coordinate sets), so the scatter is collision-free.
    """
    if maps.inv is not None and maps.inv.shape[1] == out_cap:
        return maps.inv
    k, cap = maps.in_idx.shape
    inv = jnp.full((k, out_cap), -1, jnp.int32)
    oidx = jnp.where(maps.valid, maps.out_idx, out_cap)      # OOB -> dropped
    rows = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32)[:, None],
                            (k, cap))
    return inv.at[rows.reshape(-1), oidx.reshape(-1)].set(
        maps.in_idx.reshape(-1), mode="drop")


# contraction tile of the baseline kernel: one lane width, so a 65,536-row
# resident feature column is 32 MiB of f32
BASE_CIN_TILE = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pad_rows(a: jnp.ndarray, rows: int, value=0) -> jnp.ndarray:
    if a.shape[0] == rows:
        return a
    pad = [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, pad, constant_values=value)


def _pad_cin(features: jnp.ndarray, weights: jnp.ndarray, cin_tile: int):
    """Zero-pad the contraction dim to a multiple of cin_tile; padded
    channels contribute exactly zero to every accumulator."""
    cin = features.shape[1]
    cin_pad = _round_up(cin, cin_tile)
    if cin_pad != cin:
        features = jnp.pad(features, ((0, 0), (0, cin_pad - cin)))
        weights = jnp.pad(weights, ((0, 0), (0, cin_pad - cin), (0, 0)))
    return features, weights


def window_schedule(inv: jnp.ndarray, n_rows: int, out_tile: int,
                    feat_tile: int):
    """Per-out-tile feature-window schedule for the streamed kernel.

    For each out tile: the range of feature row blocks its inverse-table
    slice touches.  wmap[o, w] = block id of sweep step w (clamped past the
    end so revisits cost no DMA); nwin[o] = number of live steps.  With
    features in packed-key order the inverse tables are monotone per offset
    and these ranges are tight — the paper's cache blocks.
    """
    k, m = inv.shape
    tiles = m // out_tile
    n_win = n_rows // feat_tile
    iv = inv.reshape(k, tiles, out_tile)
    valid = iv >= 0
    mins = jnp.min(jnp.where(valid, iv, n_rows), axis=(0, 2))
    maxs = jnp.max(jnp.where(valid, iv, -1), axis=(0, 2))
    has = maxs >= 0
    wlo = jnp.where(has, mins // feat_tile, 0).astype(jnp.int32)
    whi = jnp.where(has, maxs // feat_tile, 0).astype(jnp.int32)
    nwin = jnp.where(has, whi - wlo + 1, 0).astype(jnp.int32)
    sweep = jnp.arange(n_win, dtype=jnp.int32)
    wmap = jnp.clip(wlo[:, None] + sweep[None, :], 0, whi[:, None])
    return wmap, nwin


@functools.partial(jax.jit,
                   static_argnames=("out_cap", "out_tile", "interpret"))
def _sparse_conv_fod(features: jnp.ndarray, maps: KernelMaps,
                     weights: jnp.ndarray, out_cap: int,
                     out_tile: int, interpret: bool) -> jnp.ndarray:
    inv = invert_maps(maps, out_cap)
    m_pad = _round_up(out_cap, out_tile)
    inv = jnp.pad(inv, ((0, 0), (0, m_pad - out_cap)), constant_values=-1)
    cin_tile = None
    if features.shape[1] > BASE_CIN_TILE:
        cin_tile = BASE_CIN_TILE
        features, weights = _pad_cin(features, weights, cin_tile)
    out = spconv_fod_pallas(features, inv, weights, out_tile=out_tile,
                            cin_tile=cin_tile, interpret=interpret)
    return out[:out_cap]


def sparse_conv_fod(features: jnp.ndarray, maps: KernelMaps,
                    weights: jnp.ndarray, out_cap: int,
                    out_tile: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Drop-in replacement for core.sparseconv flows (flow='pallas').

    interpret=None auto-selects from the active backend: compiled on TPU,
    interpreter everywhere else (CPU validation).  Pass a bool to override.
    On a TPU the kernel is always compiled: a compile error raises.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _sparse_conv_fod(features, maps, weights, out_cap, out_tile,
                            interpret)


@functools.partial(jax.jit,
                   static_argnames=("out_cap", "out_tile", "feat_tile",
                                    "cin_tile", "relu", "interpret"))
def _sparse_conv_fused(features, maps, weights, out_cap, bias, ln_scale,
                       ln_bias, mask, residual, relu, out_tile, feat_tile,
                       cin_tile, interpret):
    n = features.shape[0]
    inv = invert_maps(maps, out_cap)
    m_pad = _round_up(out_cap, out_tile)
    inv = jnp.pad(inv, ((0, 0), (0, m_pad - out_cap)), constant_values=-1)
    feat_tile = min(feat_tile, _round_up(n, 8))
    n_pad = _round_up(n, feat_tile)
    features = _pad_rows(features, n_pad)
    if cin_tile is not None:
        features, weights = _pad_cin(features, weights, cin_tile)
    if mask is not None:
        mask = _pad_rows(mask.astype(features.dtype), m_pad)
    if residual is not None:
        residual = _pad_rows(residual, m_pad)
    wmap, nwin = window_schedule(inv, n_pad, out_tile, feat_tile)
    out = spconv_fod_fused_pallas(
        features, inv, weights, wmap, nwin, bias=bias, ln_scale=ln_scale,
        ln_bias=ln_bias, residual=residual, mask=mask, relu=relu,
        feat_tile=feat_tile, out_tile=out_tile, cin_tile=cin_tile,
        interpret=interpret)
    return out[:out_cap]


def sparse_conv_fused(features: jnp.ndarray, maps: KernelMaps,
                      weights: jnp.ndarray, out_cap: int,
                      epilogue: Epilogue | None = None,
                      feat_tile: int | None = None,
                      out_tile: int = 128, cin_tile: int | None = None,
                      interpret: bool | None = None) -> jnp.ndarray:
    """Streamed + fused FoD conv (flow='pallas_fused').

    feat_tile is the feature cache-block row count (None = whole cloud
    resident, clamped to the padded cloud size either way); out_tile the
    output-stationary tile; cin_tile optionally tiles the contraction dim
    (odd channel counts are zero-padded).  `epilogue` runs inside the
    kernel flush — see core.sparseconv.Epilogue.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    epi = epilogue or Epilogue()
    if (epi.ln_scale is None) != (epi.ln_bias is None):
        raise ValueError("Epilogue.ln_scale and ln_bias must come together")
    if feat_tile is None:
        feat_tile = _round_up(features.shape[0], 8)
    return _sparse_conv_fused(
        features, maps, weights, out_cap, epi.bias, epi.ln_scale,
        epi.ln_bias, epi.mask, epi.residual, bool(epi.relu), out_tile,
        feat_tile, cin_tile, interpret)


def sparse_conv_fod_ref(features, maps, weights, out_cap):
    return spconv_fod_ref(features, invert_maps(maps, out_cap), weights)
