"""Mixture-of-Experts with PointAcc-style ranking-based dispatch.

Three selectable implementations (mirroring the paper's flow ablation):

  * `dense`  — Gather-MatMul-Scatter baseline: every token through every
    expert, one-hot combine.  Maximum regularity, topk/E-fold wasted FLOPs.
  * `sorted` — single-shard Fetch-on-Demand: assignments sorted by expert
    (Mapping Unit), grouped matmul over contiguous segments
    (kernels/grouped_matmul).
  * `ep`     — production sharded version: shard_map over the `model` mesh
    axis.  Tokens are ranked into per-destination-shard segments, exchanged
    with a single all_to_all, processed by the local expert(s) as plain
    dense GEMMs (the sort bought back full MXU utilisation), and returned by
    the inverse all_to_all.  Supports E % ep == 0 (multiple experts/shard)
    and ep % E == 0 (experts replicated r times, assignments load-balanced
    across replicas by position parity — another ranking byproduct).

The aux load-balance loss (Switch-style) is returned alongside.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro import nn
from repro.configs.base import ArchConfig
from repro.kernels.grouped_matmul import ops as gmm
from repro.models.layers import act_fn


def moe_init(key, cfg: ArchConfig, d_ff: Optional[int] = None) -> nn.Params:
    d, f, e = cfg.d_model, d_ff or cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    scale_in = 1.0 / jnp.sqrt(d)
    scale_out = 1.0 / jnp.sqrt(f)
    p = {
        "router": nn.dense_init(ks[0], d, e, use_bias=False),
        "w_in": jax.random.uniform(ks[1], (e, d, f), jnp.float32,
                                   -scale_in, scale_in),
        "w_out": jax.random.uniform(ks[2], (e, f, d), jnp.float32,
                                    -scale_out, scale_out),
    }
    if cfg.gated_mlp:
        p["w_gate"] = jax.random.uniform(ks[3], (e, d, f), jnp.float32,
                                         -scale_in, scale_in)
    return p


def route(p: nn.Params, cfg: ArchConfig, x2d: jnp.ndarray):
    """x2d (T, D) -> (gates (T, topk), expert_idx (T, topk), aux_loss)."""
    logits = nn.dense(p["router"], x2d).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = lax.top_k(probs, cfg.topk)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # Switch-style aux loss: E * sum_e f_e * P_e
    e = cfg.n_experts
    hard = jnp.sum(jax.nn.one_hot(idx, e), axis=1)            # (T, E)
    f_e = jnp.mean(hard, axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return gates.astype(x2d.dtype), idx, aux


# ---------------------------------------------------------------------------
# dense baseline (Gather-MatMul-Scatter analogue)
# ---------------------------------------------------------------------------

def moe_apply_dense(p: nn.Params, cfg: ArchConfig, x: jnp.ndarray):
    b, s, d = x.shape
    x2 = x.reshape(-1, d)
    gates, idx, aux = route(p, cfg, x2)
    act = act_fn(cfg.act)
    h = jnp.einsum("td,edf->tef", x2, p["w_in"])
    if "w_gate" in p:
        h = act(jnp.einsum("td,edf->tef", x2, p["w_gate"])) * h
    else:
        h = act(h)
    y = jnp.einsum("tef,efd->ted", h, p["w_out"])
    onehot = jax.nn.one_hot(idx, cfg.n_experts,
                            dtype=gates.dtype) * gates[..., None]
    out = jnp.einsum("tke,ted->td", onehot, y)
    return out.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# single-shard sorted dispatch (Fetch-on-Demand)
# ---------------------------------------------------------------------------

def moe_apply_sorted(p: nn.Params, cfg: ArchConfig, x: jnp.ndarray,
                     capacity_factor: float = 1.5, row_tile: int = 128,
                     use_kernel: bool = False, interpret: bool = True):
    b, s, d = x.shape
    x2 = x.reshape(-1, d)
    gates, idx, aux = route(p, cfg, x2)
    out = gmm.sorted_moe_ffn(
        x2, idx, gates, p["w_in"], p["w_out"],
        w_gate=p.get("w_gate"), capacity_factor=capacity_factor,
        row_tile=row_tile, act=act_fn(cfg.act), use_kernel=use_kernel,
        interpret=interpret)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# sharded expert parallelism (shard_map over the `model` axis)
# ---------------------------------------------------------------------------

def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def make_ep_dispatch(expert_idx: jnp.ndarray, n_experts: int, ep: int,
                     cap_per_slot: int):
    """Rank assignments into (shard, local-slot, position) coordinates.

    n_slots = max(E, ep).  E >= ep: slot == expert (epl = E/ep slots per
    shard).  E < ep: each expert owns r = ep/E consecutive slots and its
    assignments round-robin across them (balanced by position parity).
    Returns (dest_row, src_token):
      dest_row (T, topk): row in the flattened (n_slots * C) send buffer,
        -1 for capacity-dropped assignments;
      src_token (n_slots * C,): source token per buffer row (-1 = padding)
        — lets the send buffer be built by GATHER instead of materialising
        a (T * topk, D) repeat + scatter (§Perf H3).
    """
    t, topk = expert_idx.shape
    a = t * topk
    r = max(1, ep // n_experts)
    n_rows = max(n_experts, ep) * cap_per_slot
    flat_e = expert_idx.reshape(-1).astype(jnp.int32)

    s_e, s_a = lax.sort((flat_e, jnp.arange(a, dtype=jnp.int32)),
                        dimension=0, num_keys=1, is_stable=True)
    seg_start = jnp.searchsorted(s_e, jnp.arange(n_experts), side="left")
    pos = jnp.arange(a, dtype=jnp.int32) - seg_start[s_e]
    slot = s_e * r + pos % r
    pos_slot = pos // r
    keep = pos_slot < cap_per_slot
    dest = jnp.where(keep, slot * cap_per_slot + pos_slot, -1)
    dest_row = jnp.full((a,), -1, jnp.int32).at[s_a].set(dest)
    src_token = jnp.full((n_rows,), -1, jnp.int32).at[
        jnp.where(keep, dest, n_rows)].set(s_a // topk, mode="drop")
    return dest_row.reshape(t, topk), src_token


# §Perf H3 toggle: token-sharded EP dispatch (the optimized layout).
# Flipped off by `dryrun --baseline` for the paper-faithful baseline table.
TOKEN_SHARDED_DEFAULT = True


def moe_apply_ep(p: nn.Params, cfg: ArchConfig, x: jnp.ndarray, *,
                 mesh, model_axis: str = "model",
                 data_spec=None, capacity_factor: float = 1.5,
                 token_sharded: bool = None):
    """x (B, S, D) with batch sharded over the data axes.

    Runs under shard_map: everything inside is per-device; the only
    communication is one all_to_all out and one back (plus psum for aux).

    token_sharded (§Perf H3): the seq dim additionally shards over the
    model axis, so each device routes/dispatches only its own tokens —
    dispatch buffers shrink by the model-axis size AND the layer consumes
    the Megatron-SP boundary layout directly (no entry all-gather).
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    ep = mesh.shape[model_axis]
    e = cfg.n_experts
    assert e % ep == 0 or ep % e == 0, (e, ep)
    epl = max(1, e // ep)           # local experts per shard
    b, s, d = x.shape
    if data_spec is None:
        # all data-parallel axes present in the production mesh
        data_spec = tuple(a for a in ("pod", "data") if a in mesh.shape)

    # per-device token count (static): batch is sharded over data axes only
    n_data = 1
    for ax in (data_spec if isinstance(data_spec, tuple) else (data_spec,)):
        if ax is None:
            continue
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            n_data *= mesh.shape[a]
    if b % n_data != 0:
        # batch not shardable over data (e.g. long-context decode):
        # keep tokens replicated over data axes
        data_spec = None
        n_data = 1
    if token_sharded is None:
        token_sharded = TOKEN_SHARDED_DEFAULT
    token_sharded = token_sharded and s % ep == 0
    seq_spec = model_axis if token_sharded else None
    n_seq = ep if token_sharded else 1
    t_loc = (b // n_data) * (s // n_seq)
    n_slots = max(e, ep)
    cap = _round_up(int(t_loc * cfg.topk * capacity_factor / n_slots) + 1, 8)

    gated = "w_gate" in p
    act = act_fn(cfg.act)

    def local_fn(xl, router_w, w_in, w_gate, w_out):
        # xl (b_loc, s_loc, d); weights already shard-local: (epl, D, F)
        bl, sl = xl.shape[0], xl.shape[1]
        x2 = xl.reshape(-1, d)
        gates, idx, aux = route({"router": {"w": router_w}}, cfg, x2)
        # aux differs per data shard (different tokens) but is replicated
        # across the model axis; return it per-shard and mean outside.
        aux = lax.pmean(aux, model_axis).reshape(1)
        dest, src_token = make_ep_dispatch(idx, e, ep, cap)   # (T, topk)

        # gather-based send building: no (T*topk, D) repeat materialised
        send = jnp.where(src_token[:, None] >= 0,
                         x2[jnp.maximum(src_token, 0)], 0)

        # (ep, epl*cap, D) -> exchange -> (ep_src, epl*cap, D)
        send = send.reshape(ep, epl * cap, d)
        recv = lax.all_to_all(send, model_axis, split_axis=0,
                              concat_axis=0, tiled=False)
        recv = recv.reshape(ep, epl, cap, d)

        outs = []
        for le in range(epl):
            rows = recv[:, le].reshape(ep * cap, d)           # one expert
            h = rows @ w_in[le]
            if gated:
                h = act(rows @ w_gate[le]) * h
            else:
                h = act(h)
            outs.append((h @ w_out[le]).reshape(ep, cap, d))
        back = jnp.stack(outs, axis=1)                        # (ep,epl,cap,D)
        back = back.reshape(ep, epl * cap, d)
        ret = lax.all_to_all(back, model_axis, split_axis=0,
                             concat_axis=0, tiled=False)
        ret = ret.reshape(n_slots * cap, d)

        picked = jnp.where(dest[..., None] >= 0,
                           ret[jnp.maximum(dest, 0)], 0.0)    # (T, topk, D)
        out = jnp.sum(picked * gates[..., None], axis=1)
        return out.reshape(bl, sl, d).astype(xl.dtype), aux

    # place weights: E >= ep -> shard expert dim; E < ep -> replicate r times
    w_in, w_out = p["w_in"], p["w_out"]
    w_gate = p.get("w_gate", jnp.zeros((e, d, 1), w_in.dtype))
    if ep > e:
        r = ep // e
        w_in = jnp.repeat(w_in, r, axis=0)
        w_out = jnp.repeat(w_out, r, axis=0)
        w_gate = jnp.repeat(w_gate, r, axis=0)

    out, aux = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(data_spec, seq_spec, None), P(None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=(P(data_spec, seq_spec, None), P(data_spec)),
        check_vma=False,
    )(x, p["router"]["w"], w_in, w_gate, w_out)
    return out, jnp.mean(aux)


def moe_apply(p, cfg, x, impl: str = "sorted", **kw):
    if impl == "dense":
        return moe_apply_dense(p, cfg, x)
    if impl == "sorted":
        return moe_apply_sorted(p, cfg, x, **kw)
    if impl == "ep":
        return moe_apply_ep(p, cfg, x, **kw)
    raise ValueError(impl)
