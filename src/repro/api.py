"""PointAccSession: one frontend over mapping, conv flows, fusion planning,
and the cross-request serving cache.

PointAcc's value is the *composition* — ranking-based mapping, streamed
sparse conv, and temporal fusion behind one accelerator interface.  This
module is that interface for the reproduction:

    from repro.api import PointAccSession

    session = PointAccSession(flow="pallas_fused")
    x = session.tensor(coords, mask, feats)          # SparseTensor
    h = session.conv(x, w_subm)                      # submanifold 3^3 conv
    h = session.conv(h, w_down, stride=2)            # strided down conv
    y = session.conv_transposed(h, w_up, stride=2)   # decoder up conv

The session owns the *policy* (mapping engine, computation flow, VMEM
budget for the fusion planner, serving-cache bound); the tensor's
`MapContext` (repro.core.tensor) owns the per-geometry *state* (sorted
clouds, kernel maps, fusion plans).  Transposed convs find their swapped
inverse maps by stride-pair lookup in the context — no caller
bookkeeping — and `MappingCache` reuses whole map pyramids across
requests with identical geometry (digest-keyed, LRU-bounded).

The dense mapping ops the PointNet-family heads need (FPS / kNN / ball
query — all ranking-based, paper Table 1) are exposed on the session too,
so one object fronts every Mapping Unit operation.
"""

from __future__ import annotations

import dataclasses
import warnings
from collections import OrderedDict
from typing import Any, Callable

import jax.numpy as jnp

from repro.core import mapping as M
from repro.core import pointops as P
from repro.core import sparseconv as SC
from repro.core.tensor import (MapContext, SparseTensor, geometry_digest,
                               infer_kernel_size)

FLOWS = ("gms", "fod", "pallas", "pallas_fused")


@dataclasses.dataclass(frozen=True)
class SessionConfig:
    """Session-level policy, threaded to every conv the session runs.

    flow         : computation flow for every conv (see core.sparseconv).
    engine       : mapping engine ("v2" packed keys / "v1" merge-sort /
                   None = v2 for 3-D clouds, v1 otherwise).
    fused_budget : VMEM bytes the temporal-fusion planner may spend per
                   conv site (None = core.fusion default).
    cap          : optional map capacity override (expert knob; the
                   default covers every match).
    cache_entries: LRU bound for the cross-request MappingCache.
    """

    flow: str = "fod"
    engine: str | None = None
    fused_budget: int | None = None
    cap: int | None = None
    cache_entries: int = 32

    def __post_init__(self):
        if self.flow not in FLOWS:
            raise ValueError(f"unknown flow {self.flow!r}; one of {FLOWS}")
        if self.engine not in (None, "v1", "v2"):
            raise ValueError(f"unknown engine {self.engine!r}")


class _LruCache:
    """Shared LRU mechanics (store / touch / evict / counters) behind the
    serving caches — `MappingCache` keys per-scene pyramids, the serve
    scheduler's `AssemblyCache` keys whole stacked micro-batches."""

    def __init__(self, max_entries: int):
        if max_entries < 1:
            raise ValueError(
                f"{type(self).__name__} needs max_entries >= 1")
        self.max_entries = max_entries
        self._store: OrderedDict[Any, Any] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def _lookup(self, key):
        """(value, found) with hit/miss accounting and LRU touch."""
        if key in self._store:
            self.hits += 1
            self._store.move_to_end(key)
            return self._store[key], True
        self.misses += 1
        return None, False

    def _insert(self, key, value) -> None:
        self._store[key] = value
        while len(self._store) > self.max_entries:
            self._store.popitem(last=False)
            self.evictions += 1

    def __len__(self) -> int:
        return len(self._store)

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "hit_rate": self.hit_rate, "evictions": self.evictions,
                "entries": len(self._store),
                "max_entries": self.max_entries}


class MappingCache(_LruCache):
    """LRU-bounded, digest-keyed reuse of Mapping-Unit work across requests.

    The Mapping Unit's output depends only on the coordinates, not the
    features, so repeated geometry — a parked scanner, multi-sweep
    aggregation, re-scored frames — is served from cache: one cheap
    blake2b over the coordinate bytes decides whether the ranking sort +
    key lookups run at all (~microseconds vs ~tens of ms).

    Values are whatever the builder returns (typically a jit-built level
    pyramid of concrete arrays).  Hit/miss/eviction counters are exposed
    for serving telemetry; eviction is least-recently-used.
    """

    def __init__(self, max_entries: int = 32):
        super().__init__(max_entries)

    @staticmethod
    def digest(arrays, extra=None) -> bytes:
        """Digest of the geometry bytes (`core.tensor.geometry_digest`);
        `extra` (any repr-able static metadata — bucket capacity,
        entry-point tag, ladder id) is folded into the key so the same
        coordinates padded into different serving buckets, or cached by
        different entry points, never collide."""
        return geometry_digest(arrays, extra)

    def get_by_key(self, key: bytes, build: Callable[[], Any]):
        """(value, hit) for a precomputed digest key; `build()` runs only
        on a miss.  Callers that already hashed the geometry (the serve
        scheduler hashes every admitted scene once for its composition
        keys) use this to avoid digesting the same bytes twice."""
        value, found = self._lookup(key)
        if found:
            return value, True
        value = build()
        self._insert(key, value)
        return value, False

    def get(self, key_arrays, build: Callable[[], Any], extra=None):
        """(value, hit) for the geometry identified by `key_arrays` (+
        optional static `extra` metadata, e.g. the serving bucket);
        `build()` runs only on a miss."""
        return self.get_by_key(self.digest(key_arrays, extra), build)


class AssemblyCache(_LruCache):
    """Composition-keyed reuse of *stacked* micro-batch pyramids.

    The serve scheduler stacks per-scene level pyramids into one
    (max_batch, ...) pytree per micro-batch.  On hot loops the SAME
    ordered composition recurs — a replayed stream, a parked sensor rig,
    re-scored frames — so the stacked result is cached under the ordered
    tuple of per-scene pyramid digests (plus bucket capacity, micro-batch
    width and dummy-tail length).  A hit skips the whole
    `tree_map`/`stack` pass AND the per-scene mapping-cache lookups under
    it: the micro-batch assembly cost drops to one tuple lookup.

    Same LRU discipline as `MappingCache`; the eviction counter lets
    serving telemetry tell cache churn (bound too small for the
    composition working set) from cold misses.
    """

    def __init__(self, max_entries: int = 16):
        super().__init__(max_entries)

    def lookup(self, key):
        """The cached stacked pytree for a composition key, or None (the
        miss is counted; the caller assembles and `put`s)."""
        value, found = self._lookup(key)
        return value if found else None

    def put(self, key, value) -> None:
        self._insert(key, value)


class PointAccSession:
    """The accelerator frontend: conv verbs + mapping ops + serving cache.

    One session serves many geometries; each `tensor(...)` call starts (or
    adopts) a `MapContext` holding that geometry's mapping state.  The
    session holds only policy (`SessionConfig`) and the cross-request
    `MappingCache`, so it is safe to share across requests.
    """

    def __init__(self, flow: str = "fod", engine: str | None = None,
                 fused_budget: int | None = None, cap: int | None = None,
                 cache_entries: int = 32,
                 config: SessionConfig | None = None):
        self.config = config or SessionConfig(
            flow=flow, engine=engine, fused_budget=fused_budget, cap=cap,
            cache_entries=cache_entries)
        self.maps_cache = MappingCache(self.config.cache_entries)

    # -- tensors ----------------------------------------------------------

    def tensor(self, coords: jnp.ndarray, mask: jnp.ndarray,
               feats: jnp.ndarray, stride: int = 1,
               context: MapContext | None = None) -> SparseTensor:
        """Wrap raw (coords, mask, feats) into a SparseTensor.

        Sentinel-fills invalid rows (like `mapping.make_point_cloud`) and
        attaches a fresh MapContext configured from the session — or an
        existing one (e.g. rebuilt from a cached level pyramid)."""
        pc = M.make_point_cloud(coords, mask, stride)
        ctx = context if context is not None else MapContext(
            engine=self.config.engine, cap=self.config.cap)
        ctx.register_cloud(stride, pc)
        return SparseTensor(feats, pc.coords, pc.mask, stride, ctx)

    def out_cloud(self, x: SparseTensor, stride: int = 1) -> M.PointCloud:
        """The output cloud a conv at `stride` writes to (memoized); lets
        callers build epilogues that need the output mask up front."""
        if stride == 1:
            return x.pc
        return x.context.down_cloud(x.stride, stride)

    def canonicalized(self, x: SparseTensor):
        """(x', order): rows permuted into packed-key order, reusing the
        context's ranking sort (no extra `lax.sort`).

        The streamed fused kernel wants key-sorted rows so inverse tables
        are monotone per offset and cache-block windows stay tight; the
        permuted cloud's SortedCloud is seeded for free (identity perm).
        Restore original row order with `zeros.at[order].set(out)`.
        Returns (x, None) when the packed engine doesn't apply (v1 / D!=3).
        """
        if x.context.engine != "v2" or x.ndim_spatial != 3:
            return x, None
        sc = x.context.sorted_cloud(x.stride)
        order = sc.perm
        coords = jnp.take(x.coords, order, axis=0)
        mask = jnp.take(x.mask, order)
        feats = jnp.take(x.feats, order, axis=0)
        pc = M.PointCloud(coords, mask, x.stride)
        ctx = MapContext(engine="v2", cap=x.context.cap)
        ctx.register_cloud(x.stride, M.SortedCloud(
            pc, sc.sorted_hi, sc.sorted_lo,
            jnp.arange(x.capacity, dtype=jnp.int32)))
        return SparseTensor(feats, coords, mask, x.stride, ctx), order

    # -- convolution ------------------------------------------------------

    def conv(self, x: SparseTensor, weights: jnp.ndarray, stride: int = 1,
             *, epilogue: SC.Epilogue | None = None,
             kernel_size: int | None = None) -> SparseTensor:
        """One sparse conv through the session's flow.

        kernel_size is inferred from the weight tensor's offset count when
        not given.  With an epilogue the caller owns masking
        (Epilogue.mask); without one invalid output rows are zeroed."""
        ks = kernel_size if kernel_size is not None else \
            infer_kernel_size(weights.shape[0], x.ndim_spatial)
        maps, out_pc = x.context.conv_maps(ks, x.stride, stride)
        return self._apply_conv(x, maps, out_pc, weights, epilogue,
                                x.stride * stride)

    def conv_transposed(self, x: SparseTensor, weights: jnp.ndarray,
                        stride: int = 2, *,
                        epilogue: SC.Epilogue | None = None,
                        kernel_size: int | None = None) -> SparseTensor:
        """Transposed (up-sampling) conv onto the cached finer cloud.

        The swapped maps come from the context's stride-pair lookup — the
        forward strided conv must have run through this context (a clear
        error explains the fix otherwise).  v2-built maps keep the
        scatter-free Pallas path; v1/capped maps fall back to a
        scatter-built inverse with a warning (see
        `sparseconv.sparse_conv_transposed`)."""
        ks = kernel_size if kernel_size is not None else \
            infer_kernel_size(weights.shape[0], x.ndim_spatial)
        maps, out_pc = x.context.transposed_maps(ks, x.stride, stride)
        if self.config.flow in ("pallas", "pallas_fused") \
                and maps.inv is None:
            warnings.warn(
                "transposed conv on maps without an inverse table (built "
                "with engine='v1' or an explicit cap): the Pallas flow "
                "falls back to a scatter-built inverse — rebuild with "
                "engine='v2' for the scatter-free path", stacklevel=2)
        new_stride = x.stride // stride if stride > 1 else x.stride
        return self._apply_conv(x, maps, out_pc, weights, epilogue,
                                new_stride)

    def _apply_conv(self, x: SparseTensor, maps, out_pc, weights,
                    epilogue: SC.Epilogue | None,
                    new_stride: int) -> SparseTensor:
        """Shared conv body: flow dispatch, fusion plan, masking rule."""
        out = SC.sparse_conv_apply(
            x.feats, maps, weights, out_pc.capacity, self.config.flow,
            epilogue=epilogue,
            plan=self._plan(x.context, x.feats.shape[0], weights, epilogue))
        if epilogue is None:
            out = out * out_pc.mask[:, None]
        return SparseTensor(out, out_pc.coords, out_pc.mask, new_stride,
                            x.context)

    def _plan(self, ctx: MapContext, n_in: int, weights,
              epilogue: SC.Epilogue | None):
        """Fusion-planner hook: only the fused Pallas flow consults it."""
        if self.config.flow != "pallas_fused":
            return None
        residual = epilogue is not None and epilogue.residual is not None
        return ctx.plan(n_in, weights.shape[1], weights.shape[2],
                        weights.shape[0], residual=residual,
                        budget_bytes=self.config.fused_budget)

    # -- dense mapping ops (PointNet-family heads) ------------------------

    @staticmethod
    def fps(xyz, mask, n_samples: int):
        """Farthest-point sampling (Max ranking, paper Table 1)."""
        return P.farthest_point_sampling(xyz, mask, n_samples)

    @staticmethod
    def knn(query_xyz, query_mask, ref_xyz, ref_mask, k: int, **kw):
        """k-nearest-neighbours (TopK ranking)."""
        return P.knn(query_xyz, query_mask, ref_xyz, ref_mask, k, **kw)

    @staticmethod
    def ball_query(query_xyz, query_mask, ref_xyz, ref_mask,
                   radius: float, k: int):
        """Ball query (TopK ranking over clipped distances)."""
        return P.ball_query(query_xyz, query_mask, ref_xyz, ref_mask,
                            radius, k)

    # -- serving ----------------------------------------------------------

    def cache_stats(self) -> dict:
        return self.maps_cache.stats()


# re-exported for frontend completeness: sessions hand these to conv()
Epilogue = SC.Epilogue

__all__ = ["FLOWS", "AssemblyCache", "MappingCache", "PointAccSession",
           "SessionConfig", "SparseTensor", "MapContext", "Epilogue"]
