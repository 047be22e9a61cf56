"""Blockwise (flash) attention Pallas kernel for the LM architectures.

Features needed by the assigned pool: causal masking, sliding-window
attention (mixtral / gemma2 local layers), logit soft-capping (gemma2), and
GQA (every arch).  GQA is expressed in the grid: q is viewed as
(B*Hkv, G, Sq, D) and the kv BlockSpec index_map ignores the group dim, so
one HBM->VMEM copy of each kv tile serves all G query heads (the kv tile is
"cached" in VMEM — same reuse argument as PointAcc's configurable cache).

Online-softmax accumulators (m, l, acc) are VMEM scratch — output-stationary
across kv tiles, the same never-spill-psums dataflow as the spconv kernel.
Out-of-range kv tiles (causal / window) are skipped entirely via pl.when.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


_NEG_INF = -1e30
_LANES = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int | None,
            softcap: float | None, block_q: int, block_k: int, n_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    # tile-level skip: entirely-masked kv tiles never touch the MXU
    needed = jnp.bool_(True)
    if causal:
        needed &= q_start + block_q - 1 >= k_start
    if window is not None:
        # kv tile entirely left of every query's window -> skip
        needed &= k_start + block_k - 1 >= q_start - window + 1

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (Tq, D)
        k = k_ref[0].astype(jnp.float32)                     # (Tk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # (Tq, Tk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...][:, :1]                           # (Tq, 1)
        l_prev = l_ref[...][:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                               # (Tq, Tk)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_k - 1)
    def _flush():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True,
                           window: int | None = None,
                           softcap: float | None = None,
                           scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jnp.ndarray:
    """q (B, Hq, Sq, D); k, v (B, Hkv, Skv, D); Hq % Hkv == 0.

    Sq % block_q == 0 and Skv % block_k == 0 (ops.py pads).
    """
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    qg = q.reshape(b * hkv, g, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)
    n_q, n_k = sq // block_q, skv // block_k
    grid = (b * hkv, g, n_q, n_k)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, softcap=softcap, block_q=block_q,
                          block_k=block_k, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bh, gg, iq, ik: (bh, gg, iq, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, gg, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, gg, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bh, gg, iq, ik: (bh, gg, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qg, kf, vf)
    return out.reshape(b, hq, sq, d)
