"""GPipe-style pipeline parallelism over the `pod` mesh axis.

Rationale: the cross-pod links are the slowest in the system.  Plain DP
sends a full gradient all-reduce across them every step; PP sends only the
(microbatch, seq, d_model) boundary activations — orders of magnitude less
for the large dense archs.  The multi-pod mesh therefore supports both
layouts: DP-over-pod (default, optional compressed grads) and PP-over-pod
(this module, --pipeline in the launcher).

Implementation: partial-manual shard_map over 'pod' ('data'/'model' stay
under GSPMD, so TP/SP/FSDP inside each stage are unchanged).  The layer
scan's stacked params are split into S stage chunks; each tick runs one
microbatch through the local stage and passes the boundary activation to
the next stage with `collective_permute` (bidirectional ring not needed —
a straight line).  GPipe schedule: n_micro + n_stages - 1 ticks; bubble
fraction = (S-1)/(n_micro + S - 1).  The whole schedule is a `lax.scan`,
so it differentiates: backward runs the reverse pipeline automatically.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_apply(body_fn: Callable, stage_params, x, n_micro: int,
                   axis_name: str = "pod"):
    """Run a stack of scanned bodies as a pipeline over `axis_name`.

    body_fn(params_one_body, x) -> x      (one scan body, pure)
    stage_params: stacked body params with leading dim = bodies_per_stage
                  (already shard_map-local, i.e. this stage's slice).
    x: (n_micro, micro_batch, seq, d) microbatched input (stage-0 holds the
       real input; other stages receive via permute).
    Returns (n_micro, micro_batch, seq, d) output from the LAST stage
    (other stages return zeros — caller selects).
    """
    n_stages = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    n_ticks = n_micro + n_stages - 1
    mb_shape = x.shape[1:]

    def stage_fwd(xmb):
        def scan_body(h, p_body):
            return body_fn(p_body, h), None
        out, _ = lax.scan(scan_body, xmb, stage_params)
        return out

    def tick(carry, t):
        inbuf, outputs = carry
        mb_idx = t - stage                    # microbatch this stage runs
        active = (mb_idx >= 0) & (mb_idx < n_micro)
        # stage 0 reads from x, others from the permuted input buffer
        src = jnp.where(stage == 0,
                        x[jnp.clip(mb_idx, 0, n_micro - 1)], inbuf)
        out = jnp.where(active, stage_fwd(src), jnp.zeros(mb_shape,
                                                          x.dtype))
        # last stage records its finished microbatch
        is_last = stage == n_stages - 1
        outputs = jnp.where(
            active & is_last,
            outputs.at[jnp.clip(mb_idx, 0, n_micro - 1)].set(out),
            outputs)
        # hand the activation to the next stage
        nxt = lax.ppermute(out, axis_name,
                           [(i, i + 1) for i in range(n_stages - 1)])
        return (nxt, outputs), None

    # carries vary across pipeline stages: mark them pod-varying for the
    # vma (varying-manual-axes) type system
    inbuf0 = lax.pcast(jnp.zeros(mb_shape, x.dtype), (axis_name,),
                       to="varying")
    outputs0 = lax.pcast(jnp.zeros_like(x), (axis_name,), to="varying")
    (_, outputs), _ = lax.scan(tick, (inbuf0, outputs0),
                               jnp.arange(n_ticks))
    # broadcast the last stage's outputs to every stage (masked psum: only
    # the last stage contributes)
    outputs = lax.psum(
        jnp.where(stage == n_stages - 1, outputs, 0.0), axis_name)
    return outputs


def split_stages(stacked_params, n_stages: int):
    """Split scan-stacked body params into per-stage chunks along dim 0.
    Returns params with a new leading stage dim, ready for shard_map over
    'pod'."""
    def split(x):
        nb = x.shape[0]
        assert nb % n_stages == 0, (nb, n_stages)
        return x.reshape((n_stages, nb // n_stages) + x.shape[1:])
    return jax.tree_util.tree_map(split, stacked_params)


def pipelined_forward(body_fn, params_layers, x, mesh, n_micro: int = 4):
    """Convenience wrapper: shard_map over 'pod' with auto data/model.

    x: (B, S, D) — microbatched internally along batch.
    """
    n_stages = mesh.shape["pod"]
    staged = split_stages(params_layers, n_stages)

    def local(staged_local, xb):
        # shard_map keeps the split dim as size 1: squeeze to this stage's
        # (bodies_per_stage, ...) params
        staged_local = jax.tree_util.tree_map(lambda a: a[0], staged_local)
        b = xb.shape[0]
        mb = b // n_micro
        xm = xb.reshape((n_micro, mb) + xb.shape[1:])
        out = pipeline_apply(body_fn, staged_local, xm,
                             n_micro=n_micro, axis_name="pod")
        return out.reshape(xb.shape)

    stage_spec = jax.tree_util.tree_map(lambda _: P("pod"), staged)
    return jax.shard_map(
        local, mesh=mesh, axis_names={"pod"},
        in_specs=(stage_spec, P()),
        out_specs=P(),
        check_vma=True,
    )(staged, x)
