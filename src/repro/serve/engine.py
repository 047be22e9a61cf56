"""Point-cloud serving engine: the model-side half of the serving stack.

`PointCloudEngine` fronts a `PointAccSession` (flow/engine policy + the
LRU digest-keyed `MappingCache`) with jit'd entry points for
MinkUNet-style segmentation, and — since the continuous-batching PR —
routes EVERY entry point through a capacity `BucketLadder`
(`serve.buckets`): scenes are padded up to a small geometric set of
capacities, so the jit cache holds at most one program per bucket per
entry point instead of one per distinct point count.

  * `segment(coords, mask, feats)` — one scene; padded to its bucket,
    level pyramid served from the per-scene mapping cache, predictions
    un-padded back to the caller's row count.
  * `segment_batch(coords, mask, feats)` — (B, N, ...) per-scene arrays,
    served through an internal `serve.scheduler.ServeScheduler`: the
    scenes are admitted, grouped into fixed-shape micro-batches,
    executed on the vmapped (and, multi-device, shard_map-sharded) path,
    and reassembled in submission order.
  * `levels_for(coords, mask)` — the cached Mapping-Unit pass alone; the
    batched form stacks per-scene cached pyramids, so a batch whose
    composition changes still hits the cache scene by scene.

The Mapping Unit output depends only on coordinates, so repeated geometry
(parked scanner, multi-sweep aggregation, re-scored frames) skips the
ranking sort + key lookups entirely on a cache hit.

The token-LM serving engine (`ServeEngine` and friends) lives in
`repro.serve.lm`.
"""

from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import MappingCache, PointAccSession
from repro.core import mapping as M
from repro.models import minkunet as MU
from repro.serve import buckets as BK

def _silence_cpu_donation_warning():
    """The apply entry points donate their feats operand (fresh
    host->device copy every call, so XLA may reuse the buffer for
    same-shaped temps).  CPU has no buffer donation and warns on every
    donated call — expected and not actionable, so it is silenced THERE
    ONLY; on GPU/TPU the warning stays live (an unusable donated buffer
    is a real perf signal).  Called from engine construction, not at
    import: `jax.default_backend()` initializes the backend, which must
    not happen as an import side effect (it would break
    `jax.distributed.initialize()` / platform config done after
    import)."""
    if jax.default_backend() == "cpu":
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")


class PointCloudEngine:
    """Serving frontend for MinkUNet-style sparse segmentation models.

    Owns the `PointAccSession` (policy + MappingCache), the bucket
    ladder, and the jit'd single-scene / vmapped-batch entry points the
    `ServeScheduler` executes through.  `max_batch` / `mesh` configure
    the internal scheduler behind `segment_batch` (mesh="auto" shards
    over the host's devices when there are several; single-device hosts
    run the plain vmapped path).
    """

    def __init__(self, params, n_stages: int, flow: str = "fod",
                 engine: Optional[str] = None, cache_entries: int = 32,
                 ladder: Optional[BK.BucketLadder] = None,
                 max_batch=None, mesh="auto", fault_plan=None,
                 obs=None):
        _silence_cpu_donation_warning()
        self.session = PointAccSession(flow=flow, engine=engine,
                                       cache_entries=cache_entries)
        self.params = params
        self.n_stages = n_stages
        self.ladder = ladder if ladder is not None else BK.DEFAULT_LADDER
        self._max_batch = max_batch
        self._mesh = mesh
        # chaos seam: a serve.faults.FaultPlan picked up by every
        # scheduler built over this engine (None = nothing injected)
        self.fault_plan = fault_plan
        # observability bundle (repro.obs.Observability) picked up by
        # the lazy default scheduler and the partition path; None keeps
        # both on their private metrics-only default
        self.obs = obs
        self._n_partitions = 0
        self._scheduler = None
        # stats() of the most recent segment(partition=) chunk plan
        self.last_partition_stats = None

        def build_one(coords, mask):
            return MU.build_unet_maps(M.PointCloud(coords, mask, 1),
                                      n_stages, engine=engine)

        def apply_one(levels, coords, mask, feats):
            pc = M.PointCloud(coords, mask, 1)
            logits = MU.minkunet_apply(params, pc, feats, flow=flow,
                                       levels=levels)
            return jnp.argmax(logits, -1)

        # feats (argument 3) is donated: every call ships a fresh copy of
        # the padded features, so its device buffer is free for reuse the
        # moment the conv trunk consumes it.  levels (argument 0) is NOT
        # donated — the scheduler's AssemblyCache keeps stacked pyramids
        # alive across micro-batches, and donating them would invalidate
        # cached entries on backends with real buffer donation.
        self._build = jax.jit(build_one)
        self._apply = jax.jit(apply_one, donate_argnums=(3,))
        self._apply_batch_fn = jax.vmap(apply_one)
        self._apply_batch = jax.jit(self._apply_batch_fn,
                                    donate_argnums=(3,))

    @classmethod
    def factory(cls, params, n_stages: int, **kwargs):
        """Zero-arg engine builder for pool owners (`serve.router.
        ServeRouter` gives each worker its own engine: private jit entry
        points + caches, identical params/config — so predictions are
        worker-independent while cache locality stays worker-local,
        which is what digest-affinity routing monetizes)."""

        def build() -> "PointCloudEngine":
            return cls(params, n_stages, **kwargs)

        return build

    # -- scheduler hookup -------------------------------------------------

    def scheduler(self):
        """The engine's lazily-built default `ServeScheduler` (the one
        `segment_batch` serves through); build your own for a different
        max_batch / mesh / pipeline depth / assembly-cache bound /
        deadline policy."""
        if self._scheduler is None:
            from repro.serve.scheduler import ServeScheduler
            kwargs = {} if self.obs is None else {"obs": self.obs}
            self._scheduler = ServeScheduler(self, max_batch=self._max_batch,
                                             mesh=self._mesh, **kwargs)
        return self._scheduler

    # -- mapping ----------------------------------------------------------

    def scene_key(self, coords, mask, bucket: int) -> bytes:
        """Digest identifying one already-padded scene's level pyramid in
        the mapping cache.  The serve scheduler hashes every admitted
        scene once and reuses the key both for the per-scene pyramid
        lookup and as its element of the micro-batch composition key
        (AssemblyCache)."""
        return MappingCache.digest((np.asarray(coords), np.asarray(mask)),
                                   extra=("levels", int(bucket)))

    def _levels_padded(self, coords, mask, bucket: int, key: bytes = None):
        """(levels, hit) for ONE already-padded scene; cached per scene
        with a bucket-aware key (precomputed `key` skips re-hashing)."""
        coords = np.asarray(coords)
        mask = np.asarray(mask)
        if key is None:
            key = self.scene_key(coords, mask, bucket)
        return self.session.maps_cache.get_by_key(
            key,
            lambda: jax.block_until_ready(
                self._build(jnp.asarray(coords), jnp.asarray(mask))))

    def _scene_levels(self, coords, mask):
        """(levels, hit, bucket) for one raw scene: pad to its bucket,
        then the cached build."""
        cap = self.ladder.bucket_for(np.asarray(coords).shape[0])
        c, m, _ = BK.pad_scene(coords, mask, None, cap)
        levels, hit = self._levels_padded(c, m, cap)
        return levels, hit, cap

    def levels_for(self, coords, mask, batched: bool = False):
        """(level pyramid, cache_hit) for a geometry; builds on miss.

        Every pyramid is built at the scene's BUCKET capacity (pass the
        same arrays to `segment`, which pads identically).  The batched
        form builds/caches per scene and stacks, so the hit flag is True
        only when every scene hit; changing the batch composition around
        a repeated scene still reuses that scene's pyramid.
        """
        if not batched:
            levels, hit, _ = self._scene_levels(coords, mask)
            return levels, hit
        coords = np.asarray(coords)
        mask = np.asarray(mask)
        per_scene = [self._scene_levels(coords[b], mask[b])
                     for b in range(coords.shape[0])]
        levels = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[lv for lv, _, _ in per_scene])
        return levels, all(hit for _, hit, _ in per_scene)

    # -- serving entry points ---------------------------------------------

    def segment(self, coords, mask, feats, levels=None, partition=None):
        """One scene -> (per-point class ids, mapping_cache_hit).

        The scene is padded to its ladder bucket before the jit'd apply
        (bounding retraces to one per bucket) and predictions are sliced
        back to the caller's row count.  Pass `levels` (from
        `levels_for`, built at the same bucket) to skip the cache lookup;
        the returned hit flag is then None.

        `partition` opens the city-scale path: `True`/"auto" (default
        policy) or a `repro.partition.PartitionPolicy`.  A scene too big
        for the ladder — which the seed path rejects — is then octree-
        chunked over its packed keys with exact receptive-field halos
        (`repro.partition`), each chunk served through the engine's
        scheduler as an ordinary scene, and the predictions stitched back
        into the caller's row order (halo rows dropped; rows outside
        every chunk, i.e. masked-invalid rows, come back as -1).  Chunked
        output equals the monolithic output on every valid row; a policy
        with `force=True` partitions even scenes that fit the ladder
        (parity tests and benchmarks rely on it).  The hit flag is True
        only when every chunk's pyramid came from the mapping cache.
        """
        n = np.asarray(coords).shape[0]
        if partition is not None:
            from repro.partition import PartitionPolicy
            policy = PartitionPolicy() if partition in (True, "auto") \
                else partition
            if policy.force or not self.ladder.fits(n):
                return self._segment_partitioned(coords, mask, feats,
                                                 policy)
        cap = self.ladder.bucket_for(n)
        c, m, f = BK.pad_scene(coords, mask, feats, cap)
        hit = None
        if levels is None:
            levels, hit = self._levels_padded(c, m, cap)
        preds = self._apply(levels, jnp.asarray(c), jnp.asarray(m),
                            jnp.asarray(f))
        return preds[:n], hit

    def _segment_partitioned(self, coords, mask, feats, policy):
        """Chunk-stream one oversized scene through the scheduler and
        stitch (see `segment(partition=)`).  Chunk plan telemetry lands
        in `self.last_partition_stats`."""
        from repro.partition import plan_partition
        spec = MU.halo_spec(self.params)
        plan = plan_partition(coords, mask, feats, spec=spec,
                              ladder=self.ladder, policy=policy)
        tracer = self.obs.tracer if self.obs is not None else None
        tid = None
        if tracer is not None:
            self._n_partitions += 1
            tid = f"partition:{self._n_partitions}"
            tracer.begin(tid, name="partition",
                         n_chunks=plan.n_chunks,
                         n_rows=int(plan.n_rows))
        preds, hit, errors = plan.run(self.scheduler(), tracer, tid)
        if tracer is not None:
            tracer.end(tid, outcome="ok" if not errors else "chunk_errors",
                       n_errors=len(errors))
        self.last_partition_stats = plan.stats()
        self.last_partition_stats["chunk_errors"] = len(errors)
        if errors:
            detail = "; ".join(f"chunk {i}: {err}"
                               for i, err in sorted(errors.items()))
            raise RuntimeError(
                f"segment(partition=): {len(errors)}/{plan.n_chunks} "
                f"chunks failed — {detail}")
        return jnp.asarray(preds), hit

    def segment_batch(self, coords, mask, feats, on_error: str = "raise",
                      priority: int = 0):
        """(B, N, 1+D) scenes -> ((B, N) class ids, mapping_cache_hit).

        Served through the internal `ServeScheduler`: each scene is
        admitted, micro-batched with its bucket peers, executed on the
        vmapped (multi-device: shard_map-sharded) path, and results are
        reassembled in submission order.  The hit flag is True only when
        every scene's pyramid came from the mapping cache.

        Per-scene failures (the scheduler's typed `ServeResult.error`
        taxonomy — rejected / shed / timeout / exec_failed) surface by
        `on_error`:

          * "raise" (default) — raise `RuntimeError` naming every failed
            scene index and its error;
          * "partial" — return `(preds, hit, errors)` where `errors` is
            {scene_index: ServeError} and failed scenes' prediction rows
            are filled with -1 (never a valid class id).

        The scheduler is shared (`self.scheduler()`): scenes another
        caller queued are flushed along with this batch, but their
        results stay drainable — only this call's requests are taken.

        `priority` is forwarded to every scene's `submit`: higher values
        win the scheduler's priority lanes under overload, and lanes
        below the brownout shed threshold are rejected at admission
        (surfacing here through the normal error taxonomy).
        """
        if on_error not in ("raise", "partial"):
            raise ValueError(f"on_error must be 'raise' or 'partial', "
                             f"got {on_error!r}")
        coords = np.asarray(coords)
        mask = np.asarray(mask)
        feats = np.asarray(feats)
        # stacked scenes share N: one ladder check up front, so an
        # overflow raises before any scene is admitted
        self.ladder.bucket_for(coords.shape[1])
        sched = self.scheduler()
        rids = [sched.submit(coords[b], feats[b], mask[b],
                             priority=priority)
                for b in range(coords.shape[0])]
        sched.flush()
        by_rid = sched.take(rids)
        errors = {b: by_rid[rid].error for b, rid in enumerate(rids)
                  if by_rid[rid].error is not None}
        if errors and on_error == "raise":
            detail = "; ".join(f"scene {b}: {err}"
                               for b, err in sorted(errors.items()))
            raise RuntimeError(
                f"segment_batch: {len(errors)}/{len(rids)} scenes "
                f"failed — {detail}")
        n = coords.shape[1]
        preds = np.stack([
            np.asarray(by_rid[rid].preds) if b not in errors
            else np.full(n, -1, np.int32)
            for b, rid in enumerate(rids)])
        hit = all(by_rid[rid].mapping_hit for b, rid in enumerate(rids)
                  if b not in errors)
        if on_error == "partial":
            return jnp.asarray(preds), hit, errors
        return jnp.asarray(preds), hit

    # -- telemetry --------------------------------------------------------

    def cache_stats(self) -> dict:
        return self.session.cache_stats()

    def compile_stats(self) -> dict:
        """jit-cache sizes of the engine's entry points — bounded by the
        number of ladder buckets actually seen (asserted in tier-1)."""
        from repro.serve.scheduler import _jit_cache_size
        return {"build": _jit_cache_size(self._build),
                "apply": _jit_cache_size(self._apply),
                "apply_batch": _jit_cache_size(self._apply_batch)}
