"""Logical-axis sharding rules: DP / TP / SP / EP / FSDP on the production
mesh.

Mesh axes: single-pod ("data", "model") = 16x16; multi-pod
("pod", "data", "model") = 2x16x16.

  * DP       batch over ("pod","data")
  * TP       heads / d_ff / vocab over "model" (Megatron)
  * SP       block-boundary activations: seq over "model" (Megatron-SP) —
             what makes 34B/72B activations fit at seq 4k under remat
  * EP       MoE expert dim over "model" (shard_map all_to_all in moe.py)
  * FSDP     parameter + optimizer fan-in dim over the data axes (ZeRO-3)

Every rule degrades gracefully: an axis is only applied when the dim is
divisible by the mesh axis size, so reduced/smoke configs and odd head
counts (e.g. qwen1.5 kv=40, xlstm H=4) fall back to replication on that dim.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingConfig:
    mesh: Mesh
    fsdp: bool = False            # shard params over the data axes (ZeRO-3)
    seq_parallel: bool = True     # Megatron-SP at block boundaries
    shard_seq_over_data: bool = False  # long-context decode (batch < data)
    # decode KV caches whose head dim can't shard over 'model' (MHA/MQA odd
    # head counts) shard their SEQ dim over 'model' instead and let SPMD
    # generate the flash-decoding partial-softmax combine (§Perf H1)
    kv_seq_over_model: bool = True

    @property
    def data_axes(self) -> Tuple[str, ...]:
        return tuple(a for a in ("pod", "data") if a in self.mesh.shape)

    @property
    def n_data(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.data_axes]))

    @property
    def n_model(self) -> int:
        return int(self.mesh.shape["model"])


def _div(n: Optional[int], m: int) -> bool:
    return n is not None and n % m == 0 and n >= m


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    axes = axes if isinstance(axes, tuple) else (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _maybe(mesh: Mesh, dim: int, axes):
    """Apply `axes` to a dim only if divisible; else replicate."""
    return axes if _div(dim, _axis_size(mesh, axes)) else None


# ---------------------------------------------------------------------------
# activation rules (the `shard` callback threaded through model code)
# ---------------------------------------------------------------------------

def make_shard_fn(sc: ShardingConfig):
    mesh = sc.mesh
    data = sc.data_axes if len(sc.data_axes) > 1 else \
        (sc.data_axes[0] if sc.data_axes else None)

    def shard(x, names):
        dims = dict(zip(names, x.shape))
        batch = dims.get("batch")
        spec = [None] * len(names)
        for i, nm in enumerate(names):
            d = x.shape[i]
            if nm == "batch":
                spec[i] = _maybe(mesh, d, data)
            elif nm == "seq_full":
                pass   # explicit SP gather point (placed on bf16 tensors)
            elif nm == "seq":
                if names[-1] == "d_model" and sc.seq_parallel:
                    spec[i] = _maybe(mesh, d, "model")
                elif sc.shard_seq_over_data and not _div(batch, sc.n_data):
                    spec[i] = _maybe(mesh, d, data)
            elif nm in ("heads", "kv_heads", "d_ff", "d_inner", "vocab"):
                if not (names[-1] == "d_model" and sc.seq_parallel
                        and nm != "vocab"):
                    spec[i] = _maybe(mesh, d, "model")
            # d_model / head_dim stay replicated
        # never shard the same mesh axis twice
        used = set()
        for i, s in enumerate(spec):
            axes = s if isinstance(s, tuple) else (s,) if s else ()
            if any(a in used for a in axes):
                spec[i] = None
            used.update(axes)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))

    return shard


# ---------------------------------------------------------------------------
# parameter rules (path-name dispatch)
# ---------------------------------------------------------------------------

_COL_PARALLEL = {"wq", "wk", "wv", "wi", "wg", "up", "wx", "wif",
                 "in_proj", "dt_proj", "lm_head", "head"}
_ROW_PARALLEL = {"wo", "down", "out_proj", "proj", "x_proj"}
_NORM_LEAVES = {"scale"}


def _path_names(path) -> list:
    names = []
    for k in path:
        if hasattr(k, "key"):            # DictKey
            names.append(str(k.key))
        elif hasattr(k, "name"):         # GetAttrKey (NamedTuple fields)
            names.append(str(k.name))
        elif hasattr(k, "idx"):          # SequenceKey
            names.append(str(k.idx))
    return names


def param_spec(path, shape, sc: ShardingConfig,
               stacked: bool = False) -> P:
    """Sharding spec for one parameter leaf, identified by its tree path."""
    mesh = sc.mesh
    names = _path_names(path)
    leaf = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""
    fsdp = (sc.data_axes if len(sc.data_axes) > 1 else sc.data_axes[0]) \
        if sc.fsdp and sc.data_axes else None

    core = list(shape[1:]) if stacked else list(shape)
    spec: list = [None] * len(core)

    def col2d():    # (fan_in, fan_out) -> (fsdp, model)
        spec[0] = _maybe(mesh, core[0], fsdp)
        spec[1] = _maybe(mesh, core[1], "model")

    def row2d():    # (fan_in, fan_out) -> (model, fsdp)
        spec[0] = _maybe(mesh, core[0], "model")
        spec[1] = _maybe(mesh, core[1], fsdp)

    if leaf == "emb":                       # (V, D): vocab over model
        spec[0] = _maybe(mesh, core[0], "model")
        spec[1] = _maybe(mesh, core[1], fsdp)
    elif leaf in _NORM_LEAVES or parent.startswith("norm") or \
            parent in ("n1", "n2", "final_norm", "enc_norm"):
        pass                                # replicated
    elif leaf in ("w_in", "w_gate"):        # (E, D, F)
        spec[0] = _maybe(mesh, core[0], "model")
        if spec[0] is None:
            spec[1] = _maybe(mesh, core[1], fsdp)
            spec[2] = _maybe(mesh, core[2], "model")
        else:
            spec[1] = _maybe(mesh, core[1], fsdp)
    elif leaf == "w_out":                   # (E, F, D)
        spec[0] = _maybe(mesh, core[0], "model")
        if spec[0] is None:
            spec[1] = _maybe(mesh, core[1], "model")
            spec[2] = _maybe(mesh, core[2], fsdp)
        else:
            spec[2] = _maybe(mesh, core[2], fsdp)
    elif parent == "router":
        spec[0] = _maybe(mesh, core[0], fsdp)
    elif leaf == "w" and len(core) == 2:
        if parent in _ROW_PARALLEL:
            row2d()
        else:                               # col-parallel default
            col2d()
    elif leaf == "b" and len(core) == 1:
        if parent in _COL_PARALLEL or parent not in _ROW_PARALLEL:
            spec[0] = _maybe(mesh, core[0], "model")
    elif leaf == "conv_w":                  # (k, d_inner)
        spec[1] = _maybe(mesh, core[1], "model")
    elif leaf in ("conv_b", "D"):           # (d_inner,)
        spec[0] = _maybe(mesh, core[0], "model")
    elif leaf == "A_log":                   # (d_inner, N)
        spec[0] = _maybe(mesh, core[0], "model")
    elif len(core) == 3 and leaf == "w":    # stacked conv-ish (K, Cin, Cout)
        spec[2] = _maybe(mesh, core[2], "model")

    if stacked:
        spec = [None] + spec
    return P(*spec)


def params_shardings(param_shapes, sc: ShardingConfig):
    """ShapeDtypeStruct tree -> NamedSharding tree.  Anything under a
    'layers' / 'enc_layers' / 'dec_layers' subtree is scan-stacked (leading
    body dim)."""
    def one(path, leaf):
        names = _path_names(path)
        stacked = any(n.endswith("layers") for n in names)
        return NamedSharding(sc.mesh,
                             param_spec(path, leaf.shape, sc, stacked))
    return jax.tree_util.tree_map_with_path(one, param_shapes)


# ---------------------------------------------------------------------------
# batch / decode-state rules
# ---------------------------------------------------------------------------

def batch_specs(batch_shapes, sc: ShardingConfig):
    mesh = sc.mesh
    data = sc.data_axes if len(sc.data_axes) > 1 else \
        (sc.data_axes[0] if sc.data_axes else None)

    def one(path, leaf):
        shape = leaf.shape
        spec = [None] * len(shape)
        if len(shape) >= 1 and _div(shape[0], sc.n_data):
            spec[0] = data
        elif len(shape) >= 2 and sc.shard_seq_over_data:
            # long-context: batch too small, shard the seq dim instead
            if _div(shape[1], sc.n_data):
                spec[1] = data
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, batch_shapes)


def state_specs(state_shapes, sc: ShardingConfig):
    """Decode-state tree: KV caches (nb, B, S, H, hd), SSM states, etc."""
    mesh = sc.mesh
    data = sc.data_axes if len(sc.data_axes) > 1 else \
        (sc.data_axes[0] if sc.data_axes else None)

    def one(path, leaf):
        names = _path_names(path)
        shape = leaf.shape
        spec: list = [None] * len(shape)
        batch_ok = len(shape) > 1 and _div(shape[1], sc.n_data)
        if "self_kv" in names or "cross" in names or \
                (len(shape) == 5 and names[-1] in ("k", "v")):
            # (nb, B, S, H, hd)
            if batch_ok:
                spec[1] = data
            elif _div(shape[2], sc.n_data):
                spec[2] = data            # flash-decoding: shard seq
            spec[3] = _maybe(mesh, shape[3], "model")
            if spec[3] is None and spec[2] is None and \
                    sc.kv_seq_over_model and _div(shape[2], sc.n_model):
                # H1: heads unshardable -> flash-decode over 'model'
                spec[2] = "model"
        elif names[-1] == "ssm":          # (nb, B, di, N)
            if batch_ok:
                spec[1] = data
            spec[2] = _maybe(mesh, shape[2], "model")
        elif names[-1] == "conv":         # (nb, B, k-1, di)
            if batch_ok:
                spec[1] = data
            spec[3] = _maybe(mesh, shape[3], "model")
        else:                             # mlstm / slstm scalar states
            if batch_ok:
                spec[1] = data
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, state_shapes)


def replicated(sc: ShardingConfig):
    return NamedSharding(sc.mesh, P())


# ---------------------------------------------------------------------------
# scene-axis serving rules (continuous-batching point-cloud scheduler)
# ---------------------------------------------------------------------------

def make_scene_mesh(axis: str = "scene", devices=None) -> Optional[Mesh]:
    """1-D mesh over the host's devices for scene-parallel serving.

    Returns None on a single-device host — the serve scheduler treats
    that as "run the vmapped path directly" (no shard_map), so the same
    code degrades to single-device CPU without changes.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < 2:
        return None
    return Mesh(np.asarray(devs), (axis,))


def shard_over_scenes(fn, mesh: Mesh, axis: str = "scene"):
    """shard_map a vmapped batch function over its leading scene axis.

    `fn(*args) -> out` must take arrays / pytrees whose every leaf is
    batched along axis 0 (the scene axis) and return leaves batched the
    same way; each device runs `fn` on its local B/n_devices scenes.
    The scene axis of every argument must be divisible by the mesh size —
    the scheduler guarantees this by padding micro-batches to a fixed
    scene count that is a multiple of the device count.

    The wrapper is transparent to positional `donate_argnums`: argument i
    of the returned function is argument i of `fn`, so
    `jax.jit(shard_over_scenes(fn, ...), donate_argnums=...)` donates the
    same operands the unsharded `jax.jit(fn, donate_argnums=...)` would
    (the serve scheduler donates the feats operand this way).  The
    shard_map body is built once per arity, not per call — the pipelined
    scheduler dispatches from the submit hot path.
    """
    spec = P(axis)
    bodies: dict[int, object] = {}

    def sharded(*args):
        body = bodies.get(len(args))
        if body is None:
            body = bodies[len(args)] = jax.shard_map(
                fn, mesh=mesh, in_specs=tuple(spec for _ in args),
                out_specs=spec, axis_names={axis}, check_vma=False)
        return body(*args)

    return sharded
