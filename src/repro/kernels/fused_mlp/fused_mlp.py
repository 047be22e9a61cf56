"""Temporal layer fusion kernel (PointAcc §4.2.4, Fig. 12).

PointAcc fuses consecutive FC layers by tiling the *point* dimension (FCs
are pointwise — no halos) and keeping every inter-layer activation on-chip
in an MIR-managed stack; only group-boundary tensors touch DRAM.

TPU analogue: one Pallas kernel per fusion group.  The grid walks point-dim
tiles; all fused weights are VMEM-resident (weight-stationary); the chain
h0 -> h1 -> ... -> hL is evaluated per tile entirely in VMEM/registers, and
only hL is written back.  XLA cannot do this on its own — it never fuses
across matmuls — which is exactly why the paper's MMU exists.

The fusion *plan* (#layers per group, tile size) comes from
repro.core.fusion.plan_fusion, reproducing the paper's compile-time search.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu



def _kernel(x_ref, *refs, n_layers: int, final_act: bool):
    out_ref = refs[-1]
    w_refs = refs[:-1]
    h = x_ref[...]
    for i in range(n_layers):
        w, b = w_refs[2 * i], w_refs[2 * i + 1]
        h = jnp.dot(h, w[...], preferred_element_type=jnp.float32)
        h = h + b[...][None, :]
        if i < n_layers - 1 or final_act:
            h = jnp.maximum(h, 0.0)
    out_ref[...] = h.astype(out_ref.dtype)


def fused_mlp_pallas(x: jnp.ndarray, weights: Sequence[jnp.ndarray],
                     biases: Sequence[jnp.ndarray], *,
                     tile_points: int = 512, final_act: bool = True,
                     interpret: bool = False) -> jnp.ndarray:
    """x (N, C0); weights[i] (C_i, C_{i+1}); biases[i] (C_{i+1},).

    N must be a multiple of tile_points (ops.py pads).
    """
    n, c0 = x.shape
    n_layers = len(weights)
    assert n % tile_points == 0
    c_out = weights[-1].shape[1]

    in_specs = [pl.BlockSpec((tile_points, c0), lambda i: (i, 0))]
    operands = [x]
    for w, b in zip(weights, biases):
        in_specs.append(pl.BlockSpec(w.shape, lambda i: (0, 0)))
        in_specs.append(pl.BlockSpec(b.shape, lambda i: (0,)))
        operands.extend([w, b])

    return pl.pallas_call(
        functools.partial(_kernel, n_layers=n_layers, final_act=final_act),
        grid=(n // tile_points,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_points, c_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c_out), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=f"fused_mlp_x{n_layers}",
    )(*operands)
