"""SparseTensor + MapContext: the TorchSparse-style frontend state.

TorchSparse (the paper group's inference engine) showed the right shape
for a sparse-conv frontend: a tensor that carries coords+feats+stride and
*owns its kernel-map cache*, so callers stop threading mapping state by
hand.  This module is that shape for the PointAcc reproduction:

  * `SparseTensor` — features + a masked voxel cloud + tensor stride,
    sharing one `MapContext` along a network so geometry work is never
    repeated.
  * `MapContext` — owns everything the Mapping Unit produces for one
    geometry: the `SortedCloud` ranking cache per stride level (v2
    engine), every kernel map keyed by (kernel_size, in_stride,
    out_stride), the temporal-fusion plans per conv site, and the
    stride-pair lookup that hands transposed convs their swapped maps
    without caller bookkeeping.

All mapping state is computed lazily and memoized: the first conv at a
stride level ranks the cloud (one `lax.sort`), every later conv at that
level is binary searches against the cached `SortedCloud` — the paper's
one-sort-per-level invariant, now enforced by the context instead of by
careful call-site plumbing.

`repro.api.PointAccSession` is the verb layer on top of this state.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Union

import jax.numpy as jnp
import numpy as np

from repro.core import fusion as FU
from repro.core import mapping as M

CloudEntry = Union[M.PointCloud, M.SortedCloud]


def geometry_digest(arrays, extra=None) -> bytes:
    """16-byte blake2b identity of a geometry.

    Hashes each array's shape/dtype tag + raw bytes; `extra` (any
    repr-able static metadata — bucket capacity, entry-point tag, stride)
    is folded in so identical coordinates cached under different serving
    shapes never collide.  This is the key the serving caches speak: the
    session's `MappingCache` stores one scene's level pyramid under it,
    and the serve scheduler's `AssemblyCache` keys a whole micro-batch by
    the *ordered tuple* of its scenes' digests (composition key).
    """
    h = hashlib.blake2b(digest_size=16)
    if extra is not None:
        h.update(repr(extra).encode())
    for a in arrays:
        a = np.asarray(a)
        h.update(str((a.shape, a.dtype)).encode())
        h.update(a.tobytes())
    return h.digest()


def infer_kernel_size(k: int, ndim: int) -> int:
    """Weight tensors are (K, Cin, Cout) with K = kernel_size**ndim; the
    frontend recovers kernel_size so callers don't repeat it."""
    ks = round(k ** (1.0 / ndim))
    for cand in (ks - 1, ks, ks + 1):
        if cand >= 1 and cand ** ndim == k:
            return cand
    raise ValueError(
        f"cannot infer kernel_size: {k} weight offsets is not a perfect "
        f"{ndim}-th power; pass kernel_size explicitly")


class MapContext:
    """Mapping-Unit state for one geometry, shared by every SparseTensor
    derived from it.

    clouds  : stride -> SortedCloud (v2) or PointCloud (v1)
    maps    : (kernel_size, in_stride, out_stride) -> KernelMaps
    plans   : conv-site shape -> core.fusion.ConvFusionPlan

    The (kernel_size, in_stride, out_stride) key is also the stride-pair
    table for transposed convs: an up-conv from `out_stride` back to
    `in_stride` finds the forward maps under the same key and swaps them
    (`transposed_maps`), inheriting the scatter-free inverse table when
    the v2 engine built them.
    """

    def __init__(self, engine: str | None = None, cap: int | None = None):
        if engine not in (None, "v1", "v2"):
            raise ValueError(f"unknown mapping engine {engine!r}")
        self.engine = engine
        self.cap = cap
        self.clouds: dict[int, CloudEntry] = {}
        self.maps: dict[tuple[int, int, int], M.KernelMaps] = {}
        self.plans: dict[tuple, FU.ConvFusionPlan] = {}

    # -- clouds -----------------------------------------------------------

    def register_cloud(self, stride: int, cloud: CloudEntry,
                       overwrite: bool = False) -> None:
        """Install a cloud at a stride level (no-op if one is present)."""
        pc = cloud.pc if isinstance(cloud, M.SortedCloud) else cloud
        if self.engine is None:
            self.engine = "v2" if pc.ndim_spatial == 3 else "v1"
        if overwrite or stride not in self.clouds:
            self.clouds[stride] = cloud

    def point_cloud(self, stride: int) -> M.PointCloud:
        entry = self.clouds[stride]
        return entry.pc if isinstance(entry, M.SortedCloud) else entry

    def sorted_cloud(self, stride: int) -> M.SortedCloud:
        """The stride level's ranking cache; sorts once on first demand."""
        entry = self.clouds[stride]
        if not isinstance(entry, M.SortedCloud):
            entry = M.sort_cloud(entry)
            self.clouds[stride] = entry
        return entry

    def down_cloud(self, in_stride: int, factor: int) -> M.PointCloud:
        """Output cloud of a strided conv (memoized per stride level)."""
        target = in_stride * factor
        if target not in self.clouds:
            if self.engine == "v2":
                self.clouds[target] = M.downsample_sorted(
                    self.sorted_cloud(in_stride), factor)
            else:
                self.clouds[target] = M.downsample(
                    self.point_cloud(in_stride), factor)
        return self.point_cloud(target)

    # -- kernel maps ------------------------------------------------------

    def conv_maps(self, kernel_size: int, in_stride: int,
                  factor: int = 1) -> tuple[M.KernelMaps, M.PointCloud]:
        """Maps + output cloud for a (possibly strided) conv, memoized.

        v2: binary searches against the level's SortedCloud; strided maps
        additionally carry the swapped inverse table (`inv_t`) so the
        matching transposed conv stays scatter-free.  v1: per-offset
        lexicographic merge-sort (any spatial dimensionality).
        """
        out_stride = in_stride * factor
        key = (kernel_size, in_stride, out_stride)
        if key in self.maps:
            return self.maps[key], self.point_cloud(out_stride)
        if self.engine == "v2":
            sc = self.sorted_cloud(in_stride)
            if factor == 1:
                out_sc = sc
            else:
                self.down_cloud(in_stride, factor)
                out_sc = self.sorted_cloud(out_stride)
            maps, _ = M.build_conv_maps_cached(sc, kernel_size, factor,
                                               cap=self.cap, out_sc=out_sc)
        else:
            in_pc = self.point_cloud(in_stride)
            out_pc = in_pc if factor == 1 else self.down_cloud(in_stride,
                                                               factor)
            maps = M.kernel_map(in_pc, out_pc, kernel_size, cap=self.cap)
        self.maps[key] = maps
        return maps, self.point_cloud(out_stride)

    def transposed_maps(self, kernel_size: int, coarse_stride: int,
                        factor: int) -> tuple[M.KernelMaps, M.PointCloud]:
        """Swapped maps for an up-conv from `coarse_stride` back to the
        finer level, found by stride-pair lookup of the forward maps.

        MinkowskiEngine semantics: upsampling is the inverse of the
        corresponding downsampling, so the fine output cloud must already
        exist — raise a clear error instead of inventing one.
        """
        if factor < 1 or coarse_stride % factor:
            raise ValueError(
                f"transposed stride {factor} does not divide the input "
                f"stride {coarse_stride}")
        fine_stride = coarse_stride // factor
        key = (kernel_size, fine_stride, coarse_stride)
        if key not in self.maps:
            built = sorted(self.maps) or "none"
            raise ValueError(
                f"no forward maps for stride pair {fine_stride}->"
                f"{coarse_stride} at kernel_size {kernel_size}: a "
                f"transposed conv reuses the encoder's strided maps "
                f"swapped, so the forward conv must run through this "
                f"context first (maps built so far: {built})")
        return self.maps[key].swap(), self.point_cloud(fine_stride)

    # -- fusion plans -----------------------------------------------------

    def plan(self, n_in: int, cin: int, cout: int, k: int, *,
             residual: bool = False,
             budget_bytes: int | None = None) -> FU.ConvFusionPlan:
        """Memoized `core.fusion.plan_conv_epilogue` for one conv site."""
        budget = budget_bytes or FU.VMEM_LIMIT_BYTES
        key = (n_in, cin, cout, k, residual, budget)
        if key not in self.plans:
            self.plans[key] = FU.plan_conv_epilogue(
                n_in, cin, cout, k, residual=residual, budget_bytes=budget)
        return self.plans[key]


@dataclasses.dataclass(frozen=True)
class SparseTensor:
    """Features + masked voxel cloud + tensor stride + shared MapContext.

    `feats` rows align with `coords`/`mask` rows; invalid rows carry the
    coordinate sentinel and zero features.  Derivative tensors produced by
    convs share the same context, so the whole network reuses one
    geometry's mapping work.
    """

    feats: jnp.ndarray          # (N, C)
    coords: jnp.ndarray         # (N, 1+D) int32, sentinel-filled
    mask: jnp.ndarray           # (N,) bool
    stride: int = 1
    context: MapContext = dataclasses.field(default_factory=MapContext,
                                            repr=False, compare=False)

    @property
    def pc(self) -> M.PointCloud:
        return M.PointCloud(self.coords, self.mask, self.stride)

    @property
    def capacity(self) -> int:
        return self.coords.shape[0]

    @property
    def ndim_spatial(self) -> int:
        return self.coords.shape[1] - 1

    @property
    def num_channels(self) -> int:
        return self.feats.shape[-1]

    def num_valid(self) -> jnp.ndarray:
        return jnp.sum(self.mask.astype(jnp.int32))

    def with_feats(self, feats: jnp.ndarray) -> "SparseTensor":
        """Same geometry (and context), new features."""
        return dataclasses.replace(self, feats=feats)

    def padded_to(self, capacity: int) -> "SparseTensor":
        """Row-pad the tensor up to a serving-bucket capacity.

        Padding rows carry SENTINEL coordinates, a False mask and zero
        features, so they sort to the end of the ranking structure and
        never enter a kernel map — valid-row outputs are unchanged.  The
        padded tensor starts a fresh MapContext (same engine/cap policy):
        cached maps are capacity-shaped and cannot be reused.
        """
        if capacity < self.capacity:
            raise ValueError(
                f"cannot pad a capacity-{self.capacity} tensor down to "
                f"{capacity}; buckets only grow")
        if capacity == self.capacity:
            return self
        pad = capacity - self.capacity
        coords = jnp.concatenate(
            [self.coords,
             jnp.full((pad, self.coords.shape[1]), M.SENTINEL, jnp.int32)])
        mask = jnp.concatenate([self.mask, jnp.zeros(pad, bool)])
        feats = jnp.concatenate(
            [self.feats, jnp.zeros((pad,) + self.feats.shape[1:],
                                   self.feats.dtype)])
        ctx = MapContext(engine=self.context.engine, cap=self.context.cap)
        pc = M.PointCloud(coords, mask, self.stride)
        ctx.register_cloud(self.stride, pc)
        return SparseTensor(feats, coords, mask, self.stride, ctx)


def from_point_cloud(pc: M.PointCloud, feats: jnp.ndarray,
                     context: MapContext | None = None) -> SparseTensor:
    """Wrap an existing PointCloud (already sentinel-filled) + features."""
    ctx = context if context is not None else MapContext()
    ctx.register_cloud(pc.stride, pc)
    return SparseTensor(feats, pc.coords, pc.mask, pc.stride, ctx)
