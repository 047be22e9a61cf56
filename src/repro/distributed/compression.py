"""Cross-pod gradient compression with error feedback.

The multi-pod mesh's leading "pod" axis rides DCN-class links that are an
order of magnitude slower than in-pod ICI, and in plain DP they carry a full
gradient all-reduce every step.  This module replaces that exchange with:

    v   = g_pod_local + error            (error feedback, Seide et al.)
    q   = int8 per-block quantise(v)
    sum = all_gather(q) over 'pod' -> local dequant-sum
    error' = v - dequant(q)

Wire bytes per step drop 8x vs f32 all-reduce (int8 payload + f32
per-block scales at 1/256 granularity; all_gather over pod=2 moves the same
payload an all-reduce would).  Error feedback makes the scheme contractive:
quantisation noise is re-injected next step instead of lost, preserving
convergence (verified in tests/test_distributed.py on the debug mesh).

Integration: `hierarchical_grads` wraps a per-pod loss gradient in a
partial-manual shard_map (only the 'pod' axis is manual; 'data'/'model'
stay under GSPMD), so in-pod reduction is still XLA's fused reduce-scatter
and ONLY the cross-pod hop is compressed.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

BLOCK = 256


def _quantize_int8(v: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-block symmetric int8: returns (q int8, scales f32)."""
    flat = v.reshape(-1)
    pad = (-flat.shape[0]) % BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequantize(q: jnp.ndarray, scale: jnp.ndarray,
                shape, dtype) -> jnp.ndarray:
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape).astype(dtype)


def compressed_psum(x: jnp.ndarray, axis_name: str,
                    error: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Error-feedback int8 mean over `axis_name` (call inside shard_map).

    Returns (mean, new_error)."""
    n = lax.axis_size(axis_name)
    v = x.astype(jnp.float32) + error
    q, scale = _quantize_int8(v)
    new_error = v - _dequantize(q, scale, x.shape, jnp.float32)
    # wire: int8 payload + f32 scales (1/256 overhead)
    q_all = lax.all_gather(q, axis_name)            # (n, blocks, BLOCK) int8
    s_all = lax.all_gather(scale, axis_name)
    total = jnp.sum(q_all.astype(jnp.float32) * s_all, axis=0)
    flat = (total / n).reshape(-1)
    k = 1
    for d in x.shape:
        k *= d
    mean = flat[:k].reshape(x.shape).astype(x.dtype)
    return mean, new_error.astype(jnp.float32)


def init_error_buffers(grad_shapes, n_pods: int = 2) -> Any:
    """Per-pod error-feedback buffers, pod-stacked on the leading dim."""
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros((n_pods,) + tuple(g.shape), jnp.float32),
        grad_shapes)


def hierarchical_grads(grad_fn, mesh, params, batch, errors):
    """Per-pod gradients + compressed cross-pod exchange.

    grad_fn(params, batch) -> (grads, metrics) computed over the pod-LOCAL
    half of the batch (in-pod DP/TP handled by GSPMD as usual).
    Returns (mean grads, new error buffers, metrics).
    """
    if "pod" not in mesh.shape:
        grads, metrics = grad_fn(params, batch)
        return grads, errors, metrics

    n_pods = mesh.shape["pod"]

    def local(params, batch, errors):
        # shard_map keeps split dims as size 1: squeeze pod-local leading.
        # Params MUST arrive pod-varying (stacked + P('pod')): if they were
        # replicated, jax.grad's vma transpose would insert an implicit
        # full-precision psum over 'pod' — silently bypassing compression.
        params = jax.tree_util.tree_map(lambda x: x[0], params)
        batch = jax.tree_util.tree_map(lambda x: x[0], batch)
        errors = jax.tree_util.tree_map(lambda x: x[0], errors)
        grads, metrics = grad_fn(params, batch)
        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_e = treedef.flatten_up_to(errors)
        out_g, out_e = [], []
        for g, e in zip(flat_g, flat_e):
            m, e2 = compressed_psum(g, "pod", e)
            out_g.append(m[None])     # vma: pod-varying -> stacked out
            out_e.append(e2[None])
        metrics = jax.tree_util.tree_map(
            lambda x: lax.pmean(x, "pod")[None], metrics)
        return (jax.tree_util.tree_unflatten(treedef, out_g),
                jax.tree_util.tree_unflatten(treedef, out_e), metrics)

    # only 'pod' is manual; 'data'/'model' sharding stays with GSPMD.
    # grads come back pod-stacked (identical rows, int8-exchanged) -> [0].
    pod = jax.tree_util.tree_map(lambda _: P("pod"), params)
    batch_spec = jax.tree_util.tree_map(lambda _: P("pod"), batch)
    err_spec = jax.tree_util.tree_map(lambda _: P("pod"), errors)
    batch_stacked = jax.tree_util.tree_map(
        lambda x: x.reshape((n_pods, x.shape[0] // n_pods) + x.shape[1:]),
        batch)
    params_stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_pods,) + x.shape), params)
    grads, new_err, metrics = jax.shard_map(
        local, mesh=mesh, axis_names={"pod"},
        in_specs=(pod, batch_spec, err_spec),
        out_specs=(pod, err_spec, P("pod")),
        check_vma=True,
    )(params_stacked, batch_stacked, errors)
    grads = jax.tree_util.tree_map(lambda g: g[0], grads)
    metrics = jax.tree_util.tree_map(lambda m: m[0], metrics)
    return grads, new_err, metrics
