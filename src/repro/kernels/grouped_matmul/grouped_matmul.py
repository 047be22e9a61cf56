"""Grouped (per-expert) matmul kernel — the PointAcc paradigm applied to MoE.

MoE token routing is a mapping operation in the paper's exact sense: tuples
(token i, expert e, weight W_e) play the role of (p_i, q_k, w_n).  We build
the maps with the ranking kernel (sort tokens by expert id — Mapping Unit)
and consume them with this kernel (Fetch-on-Demand — MMU/MXU):

  * tokens arrive sorted by expert, each expert segment padded to a multiple
    of the row tile, so every row tile belongs to exactly one expert;
  * the expert id per row tile is a *scalar-prefetched* operand whose value
    drives the weight BlockSpec index_map — the hardware analogue is the
    MMU's address generator consuming map metadata (paper Fig. 7 top);
  * expert weights stream HBM->VMEM only for tiles that need them
    (fetch-on-demand), tokens are read exactly once, outputs written exactly
    once — no gathered intermediate ever exists in HBM.

Grid: (row_tiles, cout_tiles, cin_tiles) with cin innermost, accumulating
output-stationary in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu



def _kernel(eid_ref, x_ref, w_ref, o_ref, acc_ref, *, n_ci):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(ci == n_ci - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul_pallas(x: jnp.ndarray, tile_eid: jnp.ndarray,
                          weights: jnp.ndarray, *, row_tile: int = 128,
                          cin_tile: int | None = None,
                          cout_tile: int | None = None,
                          interpret: bool = False) -> jnp.ndarray:
    """x (R, Cin) rows sorted+padded by expert; tile_eid (R//row_tile,) int32;
    weights (E, Cin, Cout) -> (R, Cout)."""
    r, cin = x.shape
    e, _, cout = weights.shape
    assert r % row_tile == 0
    cin_tile = cin_tile or cin
    cout_tile = cout_tile or cout
    assert cin % cin_tile == 0 and cout % cout_tile == 0
    n_ci = cin // cin_tile

    grid = (r // row_tile, cout // cout_tile, n_ci)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, cin_tile),
                         lambda i, co, ci, eid: (i, ci)),
            pl.BlockSpec((1, cin_tile, cout_tile),
                         lambda i, co, ci, eid: (eid[i], ci, co)),
        ],
        out_specs=pl.BlockSpec((row_tile, cout_tile),
                               lambda i, co, ci, eid: (i, co)),
        scratch_shapes=[pltpu.VMEM((row_tile, cout_tile), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_ci=n_ci),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((r, cout), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="grouped_matmul_fod",
    )(tile_eid, x, weights)
