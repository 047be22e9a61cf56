"""Flash-decoding kernel: single-token attention against a long KV cache.

The H1 hillclimb showed decode is dominated by KV-cache traffic; on real
TPU the remaining memory term is this kernel's to win: it streams the cache
HBM->VMEM exactly once in (block_s, hd) tiles, keeps the (G, hd) online-
softmax accumulator in VMEM, and masks invalid slots from the per-sequence
`length` operand (scalar-prefetched, so tiles beyond the current length are
skipped without reading the cache — the same pl.when tile-skip as the
prefill flash kernel).

Ring-buffer SWA caches work unchanged: every slot is valid once the ring
has wrapped, and `length` handles the warm-up phase (the wrapper passes
min(pos+1, window)).

Grid: (B * Hkv, S_tiles); GQA handled by keeping all G query heads of one
kv head in the q block (they share every kv tile).
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


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, softcap, block_s: int, n_s: int):
    bh = pl.program_id(0)
    it = pl.program_id(1)
    length = len_ref[bh]

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(it * block_s < length)      # skip tiles beyond the length
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (G, hd)
        k = k_ref[0].astype(jnp.float32)                  # (block_s, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        kpos = it * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], block_s), 1)
        s = jnp.where(kpos < length, s, _NEG_INF)         # (G, block_s)

        m_prev = m_ref[...][:, :1]
        l_prev = l_ref[...][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                  # (block_s, hd)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(it == n_s - 1)
    def _flush():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_decode_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        lengths: jnp.ndarray, *,
                        softcap: float | None = None,
                        scale: float | None = None, block_s: int = 256,
                        interpret: bool = False) -> jnp.ndarray:
    """q (B, Hq, hd); k/v (B, S, Hkv, hd); lengths (B,) int32 -> (B, Hq, hd).

    S must be a multiple of block_s (ops.py pads the cache)."""
    b, hq, hd = q.shape
    _, s, hkv, _ = k.shape
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    assert s % block_s == 0
    n_s = s // block_s

    qg = q.reshape(b * hkv, g, hd)
    # (B, S, Hkv, hd) -> (B*Hkv, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, hd)
    len_bh = jnp.repeat(lengths.astype(jnp.int32), hkv)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n_s),
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda bh, it, L: (bh, 0, 0)),
            pl.BlockSpec((1, block_s, hd), lambda bh, it, L: (bh, it, 0)),
            pl.BlockSpec((1, block_s, hd), lambda bh, it, L: (bh, it, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda bh, it, L: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g, _LANES), jnp.float32),
            pltpu.VMEM((g, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, softcap=softcap,
                          block_s=block_s, n_s=n_s),
        grid_spec=gs,
        out_shape=jax.ShapeDtypeStruct((b * hkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode",
    )(len_bh, qg, kf, vf)
    return out.reshape(b, hq, hd)
