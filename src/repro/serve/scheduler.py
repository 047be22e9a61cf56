"""Continuous-batching serve scheduler for point-cloud segmentation.

The missing piece between the jit'd vmapped serving path (PR 3) and real
traffic: scenes arrive one at a time with heterogeneous point counts, but
a compiled program wants fixed shapes and the accelerator wants full
batches.  `ServeScheduler` closes the gap — and since the hot-loop PR it
is a small *pipelined runtime*, not a synchronous loop:

  * **admission** — `submit()` validates each scene up front
    (`serve.faults.validate_scene`: shapes, dtypes, finite features, the
    packed-key coordinate budget, the ladder fit) and refuses bad input
    with a typed `rejected` result instead of crashing mid-pipeline;
    accepted scenes are padded to their capacity bucket
    (`serve.buckets.BucketLadder`), digested once, and queued with their
    bucket peers.  Bounded backlog (`max_backlog`) sheds the newest
    request with a `shed` result when a bucket backs up; a per-request
    `deadline_s` converts overdue queued requests into `timeout`
    results.  `submit` is thread-safe, so producers can admit scenes
    WHILE a micro-batch executes;
  * **grouping** — a bucket queue that reaches its `max_batch` width
    (per-bucket overrides supported) executes immediately as one
    micro-batch; `flush()` runs stragglers with fully-masked dummy
    scenes; `max_wait_s` adds a deadline — a partial micro-batch executes
    once its oldest queued request has waited that long (checked in
    `submit()`/`poll()` and by the background watchdog).  Every
    execution of a bucket has the SAME (max_batch, bucket_capacity)
    shape, so compilations stay bounded by the number of buckets;
  * **assembly** — per-scene level pyramids come from the session's
    digest-keyed `MappingCache`, and the *stacked* micro-batch pytree is
    cached one level up in a composition-keyed `AssemblyCache`
    (`repro.api`): a hot loop replaying the same ordered batch
    composition skips the whole `tree_map`/`stack` pass, and dummy-fill
    tails are pre-stacked once per (bucket, n_dummies).  Host staging
    goes through preallocated per-(bucket, max_batch) arenas filled in
    place — no per-batch `np.stack`;
  * **execution** — through the engine's `jax.vmap`-over-scenes path
    (feats operand donated), optionally wrapped in `shard_map` over a
    scene-axis device mesh; dispatch is ASYNC: `_run_bucket` parks an
    in-flight slot (double-buffered, `pipeline_depth` per bucket) instead
    of blocking, so assembling micro-batch i+1 overlaps executing
    micro-batch i.  `pipeline_depth=0` restores the synchronous path
    (with `assembly_cache_entries=0` it is bit-for-bit the PR-4
    scheduler — the baseline `benchmarks/bench_serve.py` measures
    against);
  * **failure isolation** — a dispatch whose device wait raises does NOT
    poison the FIFO: the slot is dropped, its requests are retried as
    fresh dispatches (bisected into halves when the batch held several
    scenes, isolating a single poison scene in O(log max_batch)
    rounds), and a request that exhausts its `max_retries` re-dispatch
    budget completes with a typed `exec_failed` result while the
    scheduler keeps serving.  `serve.faults.FaultPlan` is the injectable
    chaos seam the policy is tested with;
  * **completion** — in-flight slots retire in `drain()` / `poll()` /
    `flush()` / `take()`; `poll()` retires only slots whose results are
    already on host (non-blocking pipeline tick), `drain()`/`take()`
    block for everything in flight, and the background watchdog
    (`watchdog_s`, a `launch.fault_tolerance.Ticker`) retires ready
    slots and fires `max_wait_s` deadline flushes on an *idle*
    scheduler, so `poll()` is truly constant-time.  Results complete out
    of submission order with per-request latency, padding and cache
    telemetry; errors arrive as `ServeResult.error` (typed taxonomy:
    rejected / shed / timeout / exec_failed) — no exception escapes
    `submit`/`poll`/`drain`/`take`/`serve`.  `stats()` aggregates the
    serving picture (padding overhead %, cache hit rates, per-bucket
    occupancy, deadline flushes, compile counts, fault counters).
    `close()` (or the context manager) drains in-flight work and joins
    the watchdog thread.
"""

from __future__ import annotations

import dataclasses
import math
import random
import threading
import time
from collections import OrderedDict, deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import AssemblyCache
from repro.core import mapping as M
from repro.distributed import sharding as SH
from repro.launch import fault_tolerance as FT
from repro.obs import Observability
from repro.serve import buckets as BK
from repro.serve import faults as FLT
from repro.serve import overload as OV
from repro.serve.faults import ServeError

DEFAULT_PIPELINE_DEPTH = 2
DEFAULT_ASSEMBLY_ENTRIES = 16
DEFAULT_MAX_RETRIES = 2
_MIN_WATCHDOG_S = 0.005


@dataclasses.dataclass(frozen=True)
class ServeRequest:
    """One admitted scene, already padded to its bucket capacity."""

    rid: int
    coords: np.ndarray          # (bucket, 1+D) int32, sentinel-padded
    mask: np.ndarray            # (bucket,) bool
    feats: np.ndarray           # (bucket, C)
    n_points: int               # caller's row count (pre-padding)
    n_valid: int                # unmasked rows (what the bucket serves)
    bucket: int                 # capacity bucket the scene landed in
    t_submit: float
    key: bytes = None           # pyramid digest (None on the legacy path)
    deadline: float | None = None   # absolute monotonic queue deadline
    priority: int = 0           # lane: higher dispatches first at flush


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """One served scene, un-padded back to the caller's row count.

    Exactly one of `preds` / `error` is set: a request either completes
    with predictions or with a typed `ServeError` (rejected / shed /
    timeout / exec_failed) — the stream survives either way.
    """

    rid: int
    preds: np.ndarray | None    # (n_points,) int32 class ids; None on error
    n_points: int
    bucket: int                 # -1 when the scene never reached a bucket
    padding_frac: float         # dead fraction of the bucket's rows
                                # (padding + pre-masked rows)
    mapping_hit: bool           # scene's level pyramid came from cache
                                # (per-scene hit, or via a whole-batch
                                # assembly-cache hit)
    latency_s: float            # submit -> result (queue wait included)
    error: ServeError | None = None

    @property
    def ok(self) -> bool:
        return self.error is None


@dataclasses.dataclass
class _InFlight:
    """One dispatched, not-yet-retired micro-batch."""

    cap: int
    reqs: list                  # real requests only (dummies carry none)
    hits: list                  # per-request mapping/assembly hit flags
    preds: object               # (max_batch, cap) device array, un-waited
    dispatch_id: int = 0        # global dispatch ordinal (fault seam key)
    retries: int = 0            # redispatch generation (0 = fresh)


class _HostArena:
    """Preallocated host staging buffers for one (bucket, max_batch).

    Micro-batches are filled in place (no per-batch `np.stack`
    allocation), rotating over `depth` slots so assembling batch i+1
    never touches the slot batch i was shipped from — the host half of
    the double buffer.  feats is allocated lazily on first fill (channel
    count and dtype come from traffic, not config).  Slots are refilled
    in place, so `stage` copies a slot to the device and waits for the
    copy: on the CPU backend `jnp.asarray` (and `device_put`, whatever
    `may_alias` says) aliases the slot's memory, and an unfinished copy
    still reads it, so the next fill would rewrite an in-flight or cached
    batch.
    """

    def __init__(self, depth: int, max_batch: int, cap: int,
                 coord_dim: int):
        self.depth = max(1, depth)
        self.coords = np.full((self.depth, max_batch, cap, coord_dim),
                              M.SENTINEL, np.int32)
        self.mask = np.zeros((self.depth, max_batch, cap), bool)
        self.feats = None
        self._slot = -1

    def next_slot(self, feats_like: np.ndarray) -> int:
        # reallocate on a channel-count/dtype change so a mixed stream is
        # staged at the caller's dtype (no silent in-place downcast) —
        # exactly like the per-batch np.stack path would behave
        shape = self.mask.shape + feats_like.shape[1:]
        if self.feats is None or self.feats.shape != shape \
                or self.feats.dtype != feats_like.dtype:
            self.feats = np.zeros(shape, feats_like.dtype)
        self._slot = (self._slot + 1) % self.depth
        return self._slot

    @staticmethod
    def stage(slot: np.ndarray) -> jax.Array:
        return jax.block_until_ready(jnp.array(slot, copy=True))


def _jit_cache_size(fn) -> int:
    try:
        return int(fn._cache_size())
    except Exception:
        return -1


def _is_ready(x) -> bool:
    try:
        return all(leaf.is_ready()
                   for leaf in jax.tree_util.tree_leaves(x))
    except Exception:           # non-jax leaves / older runtimes
        return True


class ServeScheduler:
    """Bucketed continuous batching in front of a `PointCloudEngine`.

    The engine owns the model + session (flow/engine policy, MappingCache)
    and the jit'd per-scene and vmapped entry points; the scheduler owns
    the traffic: queues per capacity bucket, fixed-shape micro-batches,
    the composition-keyed assembly cache, the in-flight pipeline, the
    sharded executor, the failure-isolation policy, and serving
    telemetry.

    mesh="auto" picks a scene-axis mesh over the host's devices
    (`sharding.make_scene_mesh`) and runs micro-batches through
    `shard_map`; on a single-device host it resolves to None and the
    plain vmapped path runs — same code, no changes.  Every micro-batch
    width is rounded up to a multiple of the device count so the scene
    axis always divides the mesh.

    max_batch              : int, {capacity: width, "default": w} dict,
                             or None (ladder-level `BucketLadder.max_batch`
                             config, else `buckets.DEFAULT_MAX_BATCH`).
    pipeline_depth         : in-flight micro-batches per bucket before
                             dispatch blocks on the oldest; 0 = fully
                             synchronous execution.
    assembly_cache_entries : LRU bound of the composition-keyed stacked-
                             pyramid cache; 0 disables the cache AND the
                             host arenas (per-batch stack — the PR-4
                             assembly path, kept as the benchmark
                             baseline).
    max_wait_s             : deadline before a partial micro-batch
                             executes anyway (None = only on flush).
    validate               : admission validation (`faults.validate_scene`)
                             on submit; malformed / oversized scenes
                             complete with a `rejected` result instead of
                             raising.  False restores the raise-on-bad-
                             input PR-5 behaviour (the bench baseline).
    max_backlog            : PER-BUCKET bound on outstanding (queued +
                             in-flight) scenes; a submit beyond it is
                             shed with a `shed` result.  None = unbounded.
                             A natural setting is
                             (pipeline_depth + 1) * max_batch.  (The
                             router's same-named knob is PER-WORKER —
                             scenes assigned to one worker across all
                             buckets; `stats()` surfaces this one as
                             `scheduler_max_backlog`.)  With an
                             `overload` controller the EFFECTIVE bound
                             tightens adaptively to
                             ceil(service_rate x deadline_headroom)
                             (never looser than this static bound).
    max_retries            : re-dispatch budget per request after a
                             failed execution (2 isolates one poison
                             scene in a micro-batch of up to 4 via
                             bisect); a request that exhausts it
                             completes with `exec_failed`.
    retry_bisect           : split a failed multi-scene batch into halves
                             on retry (poison isolation) instead of
                             retrying it whole.
    retry_backoff_s        : base of the jittered exponential backoff
                             slept before each retry dispatch —
                             generation g waits retry_backoff_s * 2^g *
                             uniform(0.5, 1.5), so a transiently sick
                             device is not hammered with immediate
                             redispatches and concurrent retriers
                             decorrelate.  The default 0 preserves the
                             immediate-retry timing (and the bench
                             baseline).  The wait releases the scheduler
                             lock, so producers keep admitting scenes
                             while a retry backs off.
    retry_backoff_seed     : seed for the backoff jitter RNG — two
                             schedulers built with the same seed produce
                             identical backoff schedules (deterministic
                             chaos tests).  None (default) keeps the
                             module-level `random` source.
    overload               : `overload.OverloadPolicy` (or True for the
                             defaults, or a pre-built
                             `OverloadController`) — attaches the
                             SLO-aware overload controller: adaptive
                             shedding from the observed service rate,
                             priority/EDF queue ordering, per-bucket
                             circuit breakers, and the brownout ladder
                             (see `serve/overload.py`).  With a
                             controller, pipeline depth is enforced by
                             DEFERRING dispatch (full batches queue
                             until a slot retires — submit never blocks
                             on a device wait) instead of by the
                             blocking depth-overflow loop; the queues
                             that build are what the priority lanes
                             order and the adaptive bound sheds.  None
                             (default) keeps every serving path
                             bit-identical to the uncontrolled
                             scheduler.
    watchdog_s             : background ticker interval — fires
                             `max_wait_s` deadline flushes, expires
                             per-request deadlines and retires ready
                             slots on an idle scheduler.  None = auto
                             (max_wait_s / 4 when max_wait_s is set,
                             else off); 0 disables.  `close()` joins it.
    fault_plan             : `faults.FaultPlan` chaos seam (tests/CI);
                             None (the default) leaves the hot path
                             bit-identical.

    `submit`/`poll`/`drain`/`take`/`flush`/`stats` are thread-safe (one
    reentrant lock around queues, caches and telemetry), so producers can
    admit scenes while earlier micro-batches execute — including while
    another thread sits in `drain()`/`flush()`: the lock is released for
    the duration of every device wait (see `_retire_oldest_locked`).
    None of them raise for per-request problems — a request always
    completes, with predictions or with a typed `ServeResult.error`.
    """

    def __init__(self, engine, max_batch=None, mesh="auto",
                 axis: str = "scene",
                 pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
                 assembly_cache_entries: int = DEFAULT_ASSEMBLY_ENTRIES,
                 max_wait_s: float | None = None,
                 validate: bool = True,
                 max_backlog: int | None = None,
                 max_retries: int = DEFAULT_MAX_RETRIES,
                 retry_bisect: bool = True,
                 retry_backoff_s: float = 0.0,
                 retry_backoff_seed: int | None = None,
                 overload=None,
                 watchdog_s: float | None = None,
                 fault_plan: FLT.FaultPlan | None = None,
                 obs: Observability | None = None,
                 instance: str = "scheduler"):
        if pipeline_depth < 0:
            raise ValueError("pipeline_depth must be >= 0")
        if max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        if retry_backoff_s < 0:
            raise ValueError("retry_backoff_s must be >= 0")
        if max_backlog is not None and max_backlog < 1:
            raise ValueError("max_backlog must be >= 1 (or None)")
        self.engine = engine
        self.ladder: BK.BucketLadder = engine.ladder
        if mesh == "auto":
            mesh = SH.make_scene_mesh(axis)
        self.mesh = mesh
        n_dev = int(np.prod(list(mesh.shape.values()))) \
            if mesh is not None else 1
        if mesh is not None:
            self._apply = jax.jit(
                SH.shard_over_scenes(engine._apply_batch_fn, mesh, axis),
                donate_argnums=(3,))
        else:
            self._apply = engine._apply_batch
        default, overrides = BK.resolve_max_batch(max_batch, self.ladder)

        def round_up(b):
            return n_dev * max(1, math.ceil(b / n_dev))

        self.max_batch = round_up(default)
        self.max_batch_overrides = {c: round_up(b)
                                    for c, b in overrides.items()}
        self.pipeline_depth = int(pipeline_depth)
        self.max_wait_s = max_wait_s
        self.validate = bool(validate)
        self.max_backlog = max_backlog
        self.max_retries = int(max_retries)
        self.retry_bisect = bool(retry_bisect)
        self.retry_backoff_s = float(retry_backoff_s)
        self._rng = random.Random(retry_backoff_seed) \
            if retry_backoff_seed is not None else random
        self.overload = OV.resolve_controller(overload)
        self.fault_plan = fault_plan if fault_plan is not None else \
            getattr(engine, "fault_plan", None)
        # the packed-key budget is only a constraint for the v2 engine
        self._check_key_budget = \
            getattr(engine.session.config, "engine", None) != "v1"
        self._legacy_assembly = assembly_cache_entries == 0
        self.assembly_cache = None if self._legacy_assembly else \
            AssemblyCache(assembly_cache_entries)

        self._lock = threading.RLock()
        # serializes retirement of the in-flight FIFO head: the waiting
        # thread drops the lock during block_until_ready (so submit()
        # stays responsive) and this condition keeps a second retirer
        # from racing past it
        self._retire_cv = threading.Condition(self._lock)
        self._retiring = False
        self._closed = False
        self._queues: OrderedDict[int, deque] = OrderedDict()
        self._completed: deque[ServeResult] = deque()
        self._inflight: deque[_InFlight] = deque()   # global dispatch FIFO
        self._arenas: dict[tuple, _HostArena] = {}
        self._dummy_levels: dict[int, object] = {}
        self._dummy_tails: dict[tuple, object] = {}
        self._next_rid = 0
        self._next_dispatch = 0
        self._attempts: dict[int, int] = {}     # rid -> failed dispatches
        self._outstanding: dict[int, int] = {}  # bucket -> admitted, live
        self._coord_dim = None                  # first-seen stream widths
        self._feat_shape = None
        self._has_deadlines = False
        self._has_priorities = False
        # telemetry: every accumulator is a child of the shared metrics
        # registry (repro.obs), bound once here so the hot path pays one
        # attribute lookup + inc — stats() below is a bit-compatible
        # view over these children.  Tracer/recorder stay None unless
        # the caller opted in (Observability.enabled()).
        self.obs = obs if obs is not None else Observability()
        self.instance = str(instance)
        self._tracer = self.obs.tracer
        self._recorder = self.obs.recorder
        reg, inst = self.obs.registry, self.instance
        self._c_submitted = reg.counter(
            "serve_requests_submitted_total",
            "scenes admitted via submit()", ("instance",)).labels(inst)
        self._c_completed = reg.counter(
            "serve_requests_completed_total",
            "requests completed (ok or typed error)",
            ("instance",)).labels(inst)
        self._c_ok = reg.counter(
            "serve_requests_ok_total",
            "requests completed with predictions", ("instance",)).labels(inst)
        fam_faults = reg.counter(
            "serve_faults_total", "typed error results by code",
            ("instance", "code"))
        self._c_faults = {c: fam_faults.labels(inst, c)
                          for c in FLT.ERROR_CODES}
        self._fam_scenes = reg.counter(
            "serve_scenes_total", "real scenes executed",
            ("instance", "bucket"))
        self._fam_batches = reg.counter(
            "serve_batches_total", "micro-batches executed",
            ("instance", "bucket"))
        self._fam_dummies = reg.counter(
            "serve_dummy_scenes_total", "dummy fill scenes executed",
            ("instance", "bucket"))
        self._m_buckets = {}            # cap -> (scenes, batches, dummies)
        self._c_points_real = reg.counter(
            "serve_points_real_total", "valid (unmasked) caller rows",
            ("instance",)).labels(inst)
        self._c_rows_issued = reg.counter(
            "serve_rows_issued_total", "bucket rows issued to the device",
            ("instance",)).labels(inst)
        self._c_deadline_flushes = reg.counter(
            "serve_deadline_flushes_total",
            "partial batches flushed by max_wait_s", ("instance",)).labels(inst)
        self._c_failed_dispatches = reg.counter(
            "serve_failed_dispatches_total",
            "micro-batch executions that raised", ("instance",)).labels(inst)
        self._c_retries = reg.counter(
            "serve_retries_total", "retry dispatches issued",
            ("instance",)).labels(inst)
        self._c_backoff = reg.counter(
            "serve_retry_backoff_seconds_total",
            "total time spent backing off before retries",
            ("instance",)).labels(inst)
        self._g_recovery = reg.gauge(
            "serve_recovery_seconds",
            "last failure -> next good retire", ("instance",)).labels(inst)
        self._h_latency = reg.histogram(
            "serve_request_latency_seconds",
            "submit -> predictions (OK results only)",
            ("instance",)).labels(inst)
        fam_errlat = reg.histogram(
            "serve_error_latency_seconds",
            "submit -> typed error result, by code", ("instance", "code"))
        self._h_errlat = {c: fam_errlat.labels(inst, c)
                          for c in FLT.ERROR_CODES}
        self._h_assembly = reg.histogram(
            "serve_assembly_seconds", "host assembly time per micro-batch",
            ("instance",)).labels(inst)
        self._h_queue_wait = reg.histogram(
            "serve_queue_wait_seconds", "admission -> dispatch",
            ("instance",)).labels(inst)
        reg.gauge("serve_queue_depth", "queued scenes (all buckets)",
                  ("instance",)).labels(inst).set_function(
            lambda: sum(len(q) for q in self._queues.values()))
        reg.gauge("serve_inflight_batches", "dispatched, un-retired slots",
                  ("instance",)).labels(inst).set_function(
            lambda: len(self._inflight))
        self._last_failure_t = None
        # trace bookkeeping (only touched when a tracer is wired in)
        self._rid_trace: dict[int, tuple[str, bool]] = {}  # rid->(tid,owned)
        self._qspans: dict[int, int] = {}    # rid -> open queue_wait span
        self._wspans: dict[int, int] = {}    # rid -> open device_wait span
        # rid -> (tid, owned, open result_wait span, outcome) of results
        # not yet handed out
        self._rspans: dict[int, tuple] = {}

        if self.overload is not None:
            self.overload.bind(self)

        if watchdog_s is None:
            if max_wait_s is not None:
                watchdog_s = max_wait_s / 4
            elif self.overload is not None:
                # the controller needs periodic ticks even when nobody
                # is polling — the estimator and the brownout ladder
                # both advance on the deadline sweep
                watchdog_s = self.overload.policy.tick_s
            else:
                watchdog_s = 0.0
        self._watchdog = FT.Ticker(
            max(_MIN_WATCHDOG_S, float(watchdog_s)), self._watchdog_tick,
            name="serve-watchdog") if watchdog_s > 0 else None

    def max_batch_for(self, cap: int) -> int:
        """Micro-batch width of one capacity bucket."""
        return self.max_batch_overrides.get(cap, self.max_batch)

    def _bucket_counters(self, cap: int):
        """(scenes, batches, dummy_scenes) counter children for one
        capacity bucket, bound on first dispatch into it."""
        m = self._m_buckets.get(cap)
        if m is None:
            b = str(cap)
            m = self._m_buckets[cap] = (
                self._fam_scenes.labels(self.instance, b),
                self._fam_batches.labels(self.instance, b),
                self._fam_dummies.labels(self.instance, b))
        return m

    # -- lifecycle --------------------------------------------------------

    def close(self) -> None:
        """Drain in-flight work and stop the watchdog.

        Queued scenes are executed (dummy-filled partial batches) and
        every in-flight micro-batch retires, so completed results stay
        drainable after close; the watchdog ticker thread is JOINED (no
        leaked daemon threads).  A chaos `FaultPlan` is closed first, so
        pending injected delays wake early and shutdown under chaos is
        prompt.  Idempotent; a submit after close completes with a
        `rejected` result instead of raising.
        """
        if self.fault_plan is not None:
            self.fault_plan.close()     # wake injected waits first
        wd, self._watchdog = self._watchdog, None
        if wd is not None:
            wd.close()                  # join OUTSIDE the lock
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._expire_overdue_locked()
            for cap in list(self._queues):
                while self._queues[cap]:
                    self._run_bucket(cap, "flush")
            while self._retire_oldest_locked():
                pass
            if self.overload is not None:
                self.overload.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # -- admission --------------------------------------------------------

    def submit(self, coords, feats, mask=None,
               deadline_s: float | None = None,
               priority: int = 0,
               trace_id: str | None = None) -> int:
        """Admit one scene; returns its request id — ALWAYS.

        `coords` (N, 1+D) int32, `feats` (N, C); `mask` defaults to all
        rows valid.  The scene is validated up front (shapes, dtypes,
        finite features, packed-key budget, ladder fit — see
        `faults.validate_scene`); a scene that fails admission completes
        immediately with a `rejected` result under the returned rid
        instead of raising.  Accepted scenes are padded to the smallest
        ladder bucket holding N rows and queued with their bucket peers;
        a bucket that reaches its `max_batch` width dispatches
        immediately (async — the call returns while the micro-batch
        executes).  `deadline_s` bounds the QUEUE wait: a request still
        queued that long later completes with a `timeout` result (a
        request already dispatched runs to completion).  With
        `max_backlog`, a submit into a backed-up bucket completes with a
        `shed` result.  Thread-safe: padding and digesting happen
        outside the lock, so concurrent producers overlap their
        admission work.

        `priority` (default 0, higher = more urgent) picks the lane:
        when any nonzero priority has been seen — or an overload
        controller is attached and deadlines are in play — each
        micro-batch takes the highest-priority queued scenes first,
        earliest deadline first within a priority (EDF), FIFO within
        ties.  Only the queue ORDER changes; per-scene predictions are
        bit-identical.  Under brownout level 3 the lanes below the
        policy's `shed_below_priority` are shed at admission.

        `trace_id` attaches this request's spans to an EXISTING trace
        (a router began it before enqueueing); the scheduler then never
        ends that trace's root — the component that began it does.
        With no tracer wired in (the default) the argument is ignored.
        """
        t_submit = time.monotonic()
        if self.fault_plan is not None:
            coords, feats, mask = self.fault_plan.on_submit(
                coords, feats, mask)
        err = None
        n, cap = 0, -1
        if self.validate:
            try:
                coords, mask, feats, n, cap = FLT.validate_scene(
                    coords, feats, mask, self.ladder,
                    check_key_budget=self._check_key_budget,
                    coord_dim=self._coord_dim,
                    feat_shape=self._feat_shape)
            except FLT.AdmissionError as e:
                err = e.as_error()
        else:
            # PR-5 behaviour (bench baseline): no validation, a ladder
            # overflow raises out of submit()
            coords = np.asarray(coords)
            n = coords.shape[0]
            if mask is None:
                mask = np.ones(n, bool)
            cap = self.ladder.bucket_for(n)
        if err is None:
            c, m, f = BK.pad_scene(coords, mask, feats, cap)
            key = None if self._legacy_assembly else \
                self.engine.scene_key(c, m, cap)
            n_valid = int(np.asarray(mask, bool).sum())
            deadline = t_submit + deadline_s \
                if deadline_s is not None else None
        tr = self._tracer
        # admission ends here, before the lock: the wait for it is
        # `lock_wait` (another thread may be mapping a micro-batch)
        t_adm = time.monotonic() if tr is not None else None
        with self._lock:
            t_locked = time.monotonic() if tr is not None else None
            rid = self._next_rid
            self._next_rid += 1
            self._c_submitted.inc()
            if err is None and self._closed:
                err = ServeError(FLT.REJECTED, "scheduler is closed")
            if err is None and self.max_backlog is not None and \
                    self._outstanding.get(cap, 0) >= self.max_backlog:
                ov = self.overload
                rate = ov.service_rate(cap) if ov is not None else None
                err = ServeError(
                    FLT.SHED,
                    f"bucket {cap} backlog at the max_backlog bound "
                    f"({self.max_backlog} outstanding scenes"
                    + (f"; observed service rate {rate:.1f} scenes/s"
                       if rate is not None else "") + ")",
                    retry_after_s=ov.retry_after(
                        cap, self._outstanding.get(cap, 0))
                    if ov is not None else None)
            if err is None and self.overload is not None:
                err = self.overload.check_admission_locked(
                    cap, self._outstanding.get(cap, 0), priority)
            if tr is not None:
                tid = trace_id if trace_id is not None else \
                    f"{self.instance}:rid:{rid}"
                tr.begin(tid, t=t_submit, rid=rid, instance=self.instance)
                self._rid_trace[rid] = (tid, trace_id is None)
                tr.span(tid, "admission", t_start=t_submit, t_end=t_adm,
                        bucket=cap, n_points=int(n))
                tr.span(tid, "lock_wait", t_start=t_adm, t_end=t_locked)
            if self._recorder is not None:
                self._recorder.record("submit", rid=rid, bucket=int(cap),
                                      instance=self.instance,
                                      rejected=err is not None)
            if err is not None:
                self._complete_error_locked(rid, n, cap, t_submit, err)
                return rid
            if tr is not None:
                sid = tr.span(tid, "queue_wait", t_start=t_locked,
                              bucket=cap)
                if sid is not None:
                    self._qspans[rid] = sid
            if self._coord_dim is None:
                self._coord_dim = int(coords.shape[1])
                self._feat_shape = tuple(np.asarray(feats).shape[1:])
            req = ServeRequest(rid, c, m, f, n, n_valid, cap,
                               t_submit, key, deadline, int(priority))
            if deadline is not None:
                self._has_deadlines = True
            if priority:
                self._has_priorities = True
            self._outstanding[cap] = self._outstanding.get(cap, 0) + 1
            self._queues.setdefault(cap, deque()).append(req)
            if len(self._queues[cap]) >= self.max_batch_for(cap):
                if self.overload is None or self.pipeline_depth == 0 \
                        or not self._bucket_at_depth_locked(cap):
                    self._run_bucket(cap, "full")
                # else: DEFERRED dispatch (controller mode) — the bucket
                # is at its pipeline depth, so the batch stays queued
                # until a slot retires (_pump_locked).  This is what
                # gives the priority/EDF lanes something to order and
                # the adaptive bound a real backlog to measure; the
                # uncontrolled scheduler keeps the PR-6 behaviour of
                # dispatching immediately and blocking in the depth
                # overflow loop instead.
            self._check_deadlines_locked()
            return rid

    def poll(self) -> list[ServeResult]:
        """Non-blocking pipeline tick: deadline-flush overdue partial
        buckets, expire overdue requests, retire in-flight micro-batches
        whose results are already on host, and hand back everything
        completed so far."""
        with self._lock:
            self._check_deadlines_locked()
            while self._retire_oldest_locked(only_ready=True):
                pass
            if self._pump_locked():
                while self._retire_oldest_locked(only_ready=True):
                    pass
            out = list(self._completed)
            self._completed.clear()
            self._handed_out(out)
            return out

    def flush(self) -> int:
        """Execute every queued scene (partial micro-batches are filled
        with masked dummy scenes), wait for everything in flight, and
        return how many scenes ran."""
        with self._lock:
            self._expire_overdue_locked()
            ran = 0
            for cap in list(self._queues):
                while self._queues[cap]:
                    ran += self._run_bucket(cap, "flush")
            while self._retire_oldest_locked():
                pass
            return ran

    def drain(self) -> list[ServeResult]:
        """Hand back every completed result, in completion order (NOT
        submission order — whichever bucket filled first ran first);
        waits for in-flight micro-batches."""
        with self._lock:
            while True:
                while self._retire_oldest_locked():
                    pass
                if not self._pump_locked():
                    break
            out = list(self._completed)
            self._completed.clear()
            self._handed_out(out)
            return out

    def take(self, rids) -> dict[int, ServeResult]:
        """Pop completed results for `rids` only; anything else stays
        drainable (lets one caller collect its requests from a shared
        scheduler without discarding another caller's results).  Waits
        for in-flight micro-batches (the rids may be on one)."""
        with self._lock:
            while True:
                while self._retire_oldest_locked():
                    pass
                if not self._pump_locked():
                    break
            want = set(rids)
            out, keep = {}, deque()
            for r in self._completed:
                if r.rid in want:
                    out[r.rid] = r
                else:
                    keep.append(r)
            self._completed = keep
            self._handed_out(out.values())
            return out

    def serve(self, scenes) -> dict[int, ServeResult]:
        """Convenience: submit an iterable of (coords, feats[, mask])
        scenes, flush, and return {rid: result} for THIS call's requests
        only — on a shared scheduler, other callers' results stay
        drainable/takeable."""
        rids = [self.submit(*scene) for scene in scenes]
        self.flush()
        return self.take(rids)

    # -- execution --------------------------------------------------------

    def _dummy_request(self, like: ServeRequest) -> ServeRequest:
        """A fully-masked scene filling the fixed scene axis: sentinel
        coords sort to the end and match nothing, so it costs one cached
        (all-sentinel) pyramid per bucket and zero result rows."""
        cap = like.bucket
        coords = np.full_like(like.coords, M.SENTINEL)
        mask = np.zeros(cap, bool)
        feats = np.zeros_like(like.feats)
        return ServeRequest(-1, coords, mask, feats, 0, 0, cap,
                            time.monotonic())

    def _dummy_pyramid(self, like: ServeRequest):
        """The bucket's all-sentinel level pyramid, built once — cached
        scheduler-side so MappingCache telemetry only counts real
        scenes."""
        cap = like.bucket
        if cap not in self._dummy_levels:
            self._dummy_levels[cap] = jax.block_until_ready(
                self.engine._build(
                    jnp.asarray(np.full_like(like.coords, M.SENTINEL)),
                    jnp.asarray(np.zeros(cap, bool))))
        return self._dummy_levels[cap]

    def _dummy_tail(self, like: ServeRequest, n_dummy: int):
        """The pre-stacked (n_dummy, ...) dummy pyramid tail for partial
        micro-batches, built once per (bucket, n_dummies)."""
        key = (like.bucket, n_dummy)
        if key not in self._dummy_tails:
            base = self._dummy_pyramid(like)
            self._dummy_tails[key] = jax.tree_util.tree_map(
                lambda x: jnp.stack([x] * n_dummy), base)
        return self._dummy_tails[key]

    def _assemble(self, reqs, cap: int, mb: int, marks: dict = None):
        """Arena + composition-cache assembly: (hits, apply operands).

        coords/mask/feats are staged in the bucket's preallocated host
        arena (rotating slot, filled in place); the stacked level-pyramid
        pytree — and the stacked coords/mask device arrays, which the
        composition key fully determines — are served from the
        AssemblyCache when the ordered composition repeats, else stacked
        once (real scenes + the pre-stacked dummy tail) and cached.  Only
        feats is re-staged on a hit: it is the one operand the key does
        not cover (same geometry, fresh sensor payload).

        `marks` (tracing only) receives monotonic timestamps of the
        phases: feats staging, the cache lookup, and on a miss the
        coords/mask staging, each scene's mapping (`mapping`, one
        (start, end) per request) and the stacking of the pyramids.
        """
        n_real, n_dummy = len(reqs), mb - len(reqs)
        if marks is not None:
            marks["arena_t0"] = time.monotonic()
        arena = self._arenas.get((cap, mb))
        if arena is None:
            arena = self._arenas[(cap, mb)] = _HostArena(
                max(1, self.pipeline_depth), mb, cap,
                reqs[0].coords.shape[1])
        s = arena.next_slot(reqs[0].feats)
        for i, r in enumerate(reqs):
            arena.feats[s, i] = r.feats
        if n_dummy:                     # clear stale rows from fuller runs
            arena.feats[s, n_real:] = 0
        feats_b = arena.stage(arena.feats[s])

        comp_key = (cap, mb, n_dummy, tuple(r.key for r in reqs))
        if marks is not None:
            marks["lookup_t0"] = time.monotonic()
        cached = self.assembly_cache.lookup(comp_key)
        if marks is not None:
            marks["lookup_t1"] = time.monotonic()
        if cached is not None:
            # the whole stacked batch is reused: every scene's mapping
            # work was skipped wholesale, so each request reports a hit
            # (the per-scene MappingCache is bypassed, not consulted)
            levels_b, coords_b, mask_b = cached
            hits = [True] * n_real
        else:
            for i, r in enumerate(reqs):
                arena.coords[s, i] = r.coords
                arena.mask[s, i] = r.mask
            if n_dummy:
                arena.coords[s, n_real:] = M.SENTINEL
                arena.mask[s, n_real:] = False
            coords_b = arena.stage(arena.coords[s])
            mask_b = arena.stage(arena.mask[s])
            mapped = None
            if marks is not None:
                marks["staged_t1"] = time.monotonic()
                mapped = marks["mapping"] = []
            per = []
            for r in reqs:
                t = time.monotonic() if mapped is not None else None
                per.append(self.engine._levels_padded(r.coords, r.mask, cap,
                                                      key=r.key))
                if mapped is not None:
                    mapped.append((t, time.monotonic()))
            hits = [h for _, h in per]
            levels_b = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *[lv for lv, _ in per])
            if n_dummy:
                levels_b = jax.tree_util.tree_map(
                    lambda a, b: jnp.concatenate([a, b]),
                    levels_b, self._dummy_tail(reqs[0], n_dummy))
            if marks is not None:
                marks["stacked_t1"] = time.monotonic()
            self.assembly_cache.put(comp_key,
                                    (levels_b, coords_b, mask_b))
        if marks is not None:
            marks["assembly_t1"] = time.monotonic()
            marks["cache_hit"] = cached is not None
        return hits, (levels_b, coords_b, mask_b, feats_b)

    def _assemble_legacy(self, reqs, cap: int, mb: int):
        """PR-4 assembly (per-batch np.stack + tree_map over per-scene
        cached pyramids) — the `assembly_cache_entries=0` baseline path
        that `bench_serve` measures the pipelined path against."""
        reqs = list(reqs)
        levels, hits = [], []
        for r in reqs:
            lv, hit = self.engine._levels_padded(r.coords, r.mask, cap)
            levels.append(lv)
            hits.append(hit)
        while len(reqs) < mb:
            d = self._dummy_request(reqs[0])
            reqs.append(d)
            levels.append(self._dummy_pyramid(d))
        levels_b = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                          *levels)
        coords_b = jnp.asarray(np.stack([r.coords for r in reqs]))
        mask_b = jnp.asarray(np.stack([r.mask for r in reqs]))
        feats_b = jnp.asarray(np.stack([r.feats for r in reqs]))
        return hits, (levels_b, coords_b, mask_b, feats_b)

    def _bucket_at_depth_locked(self, cap: int) -> bool:
        """Is this bucket's in-flight slot count at its pipeline depth?
        (The same bound the uncontrolled depth-overflow loop enforces by
        blocking — controller mode enforces it by deferring dispatch.)"""
        return sum(1 for slot in self._inflight if slot.cap == cap) \
            > self.pipeline_depth

    def _pump_locked(self) -> int:
        """Dispatch deferred full batches that now fit their bucket's
        pipeline depth (controller mode only — without a controller
        submit never defers).  Returns how many scenes were dispatched;
        callers that just retired slots loop until this returns 0."""
        if self.overload is None or self.pipeline_depth == 0:
            return 0
        ran = 0
        for cap in list(self._queues):
            q = self._queues[cap]
            while len(q) >= self.max_batch_for(cap) and \
                    not self._bucket_at_depth_locked(cap):
                ran += self._run_bucket(cap, "pump")
        return ran

    def _lane_order_enabled(self) -> bool:
        """Priority/EDF queue ordering is live once any nonzero
        priority has been submitted, or an overload controller is
        attached and deadlines are in play.  Plain FIFO streams (the
        PR-9 behaviour) never enter the reorder path — bit-identical
        dispatch composition."""
        return self._has_priorities or \
            (self.overload is not None and self._has_deadlines)

    def _run_bucket(self, cap: int, trigger: str) -> int:
        """Pop up to max_batch queued scenes and dispatch them (caller
        holds the lock); `trigger` says why (full / deadline / flush /
        pump), for the dispatch span.  With priority lanes active the
        pop takes the highest-priority scenes first, earliest deadline
        first within a priority (EDF), FIFO within ties — the
        micro-batch SHAPE and each scene's predictions are unchanged,
        only which queued scenes go first."""
        q = self._queues[cap]
        mb = self.max_batch_for(cap)
        take = min(mb, len(q))
        if take > 1 and len(q) > take and self._lane_order_enabled():
            items = list(q)
            chosen = sorted(
                range(len(items)),
                key=lambda i: (-items[i].priority,
                               items[i].deadline
                               if items[i].deadline is not None
                               else math.inf, i))[:take]
            picked = set(chosen)
            reqs = [items[i] for i in sorted(picked)]
            q.clear()
            q.extend(items[i] for i in range(len(items))
                     if i not in picked)
        else:
            reqs = [q.popleft() for _ in range(take)]
        if not reqs:
            return 0
        return self._dispatch(reqs, cap, 0, trigger)

    def _dispatch(self, reqs, cap: int, retries: int, trigger: str) -> int:
        """Assemble + dispatch one micro-batch (caller holds the lock).

        Dispatch is asynchronous: the jit call returns a future-like
        device array that is parked on the in-flight FIFO; completion
        happens in drain()/poll()/flush()/take() (or the watchdog).
        Once a bucket exceeds `pipeline_depth` in-flight slots the
        oldest slots retire first (double buffering) — with depth 0 the
        batch retires immediately (synchronous PR-4 behaviour).  Retry
        dispatches (`retries > 0`) run partial batches at the SAME
        (max_batch, capacity) shape with dummy fill, so failure recovery
        never compiles a new program.  A dispatch that raises on the
        spot (assembly or launch) goes straight to the failure-isolation
        path instead of propagating.
        """
        mb = self.max_batch_for(cap)
        n_real = len(reqs)
        did = self._next_dispatch
        self._next_dispatch += 1
        if retries:
            self._c_retries.inc()
        tr = self._tracer
        t_disp = time.monotonic()
        marks = {} if tr is not None else None
        try:
            t0 = time.perf_counter()
            if self._legacy_assembly:
                hits, operands = self._assemble_legacy(reqs, cap, mb)
            else:
                hits, operands = self._assemble(reqs, cap, mb, marks)
            t1 = time.perf_counter()
            self._h_assembly.observe(t1 - t0)
            if marks is not None:
                marks["launch_t0"] = time.monotonic()
            preds = self._apply(*operands)
            if marks is not None:
                marks["launch_t1"] = time.monotonic()
        except Exception as e:
            self._on_slot_failed(
                _InFlight(cap, list(reqs), [False] * n_real, None,
                          did, retries), e)
            return n_real
        if tr is not None:
            self._trace_dispatch(reqs, hits, did, cap, retries, trigger,
                                 t_disp, marks)
        if self._recorder is not None:
            self._recorder.record(
                "dispatch", dispatch_id=did, bucket=int(cap),
                n_real=n_real, retries=retries,
                rids=[r.rid for r in reqs], instance=self.instance)
        self._inflight.append(_InFlight(cap, list(reqs), hits, preds,
                                        did, retries))

        m_scenes, m_batches, m_dummies = self._bucket_counters(cap)
        self._c_points_real.inc(sum(r.n_valid for r in reqs))
        self._c_rows_issued.inc(mb * cap)
        m_scenes.inc(n_real)
        m_batches.inc()
        m_dummies.inc(mb - n_real)
        for r in reqs:
            self._h_queue_wait.observe(t_disp - r.t_submit)

        if self.pipeline_depth == 0:
            while self._retire_oldest_locked():
                pass
        elif self.overload is None:
            # double buffering: once this bucket exceeds its depth, pay
            # for the FIFO head (possibly an older bucket's slot — see
            # _retire_oldest_locked) until the bucket is back in budget
            while sum(1 for slot in self._inflight if slot.cap == cap) \
                    > self.pipeline_depth:
                self._retire_oldest_locked()
        # else: controller mode bounds depth at ADMISSION (deferred
        # dispatch in submit) instead of blocking here — retirement
        # belongs to poll()/flush()/take()/the watchdog, so submit never
        # sits in a device wait and the deferral decision is
        # deterministic (only a deadline flush can transiently exceed
        # the depth)
        return n_real

    def _trace_dispatch(self, reqs, hits, did: int, cap: int,
                        retries: int, trigger: str, t_disp: float,
                        marks: dict) -> None:
        """Per-request dispatch spans (caller holds the lock, tracer is
        wired in): close the queue_wait span, record the dispatch span
        with its assembly and launch children, open the device_wait
        span.  The dispatch's stacking is recorded in every request's
        tree (one `stack_levels` per `dispatch_id`); `mapping` only in
        the tree of the request whose scene it mapped."""
        tr = self._tracer
        t_launch = marks["launch_t1"]
        thread = threading.current_thread().name
        mapped = marks.get("mapping")
        for i, r in enumerate(reqs):
            tid_owned = self._rid_trace.get(r.rid)
            if tid_owned is None:
                continue
            tid = tid_owned[0]
            tr.end_span(tid, self._qspans.pop(r.rid, None), t_end=t_disp)
            dspan = tr.span(tid, "dispatch", t_start=t_disp,
                            t_end=t_launch, dispatch_id=did,
                            bucket=cap, retries=retries,
                            n_real=len(reqs), trigger=trigger,
                            thread=thread)
            if "arena_t0" in marks:
                aspan = tr.span(tid, "assembly", parent=dspan,
                                t_start=marks["arena_t0"],
                                t_end=marks["assembly_t1"],
                                cache_hit=marks["cache_hit"])
                tr.span(tid, "arena_staging", parent=aspan,
                        t_start=marks["arena_t0"],
                        t_end=marks["lookup_t0"])
                tr.span(tid, "assembly_lookup", parent=aspan,
                        t_start=marks["lookup_t0"],
                        t_end=marks["lookup_t1"])
                if mapped is not None:
                    tr.span(tid, "arena_staging", parent=aspan,
                            t_start=marks["lookup_t1"],
                            t_end=marks["staged_t1"])
                    tr.span(tid, "mapping", parent=aspan,
                            t_start=mapped[i][0], t_end=mapped[i][1],
                            rows=r.n_valid, bucket=cap,
                            cache_hit=bool(hits[i]))
                    tr.span(tid, "stack_levels", parent=aspan,
                            t_start=mapped[-1][1],
                            t_end=marks["stacked_t1"], dispatch_id=did)
            tr.span(tid, "launch", parent=dspan,
                    t_start=marks["launch_t0"], t_end=t_launch)
            sid = tr.span(tid, "device_wait", t_start=t_launch,
                          dispatch_id=did)
            if sid is not None:
                self._wspans[r.rid] = sid

    def _wait_slot(self, slot: _InFlight):
        """Block for one slot's device results (runs WITHOUT the lock).
        The fault plan's wait seam lives here: an injected delay or
        failure behaves exactly like a slow or crashing device."""
        if self.fault_plan is not None:
            self.fault_plan.check_wait(slot.dispatch_id, slot.cap,
                                       [r.rid for r in slot.reqs])
        return jax.block_until_ready(slot.preds)

    def _on_slot_failed(self, slot: _InFlight, exc: BaseException) -> None:
        """Failure isolation (caller holds the lock): a failed
        micro-batch never re-enters the FIFO to poison later retires.

        Every real request on the slot gets another chance as a fresh
        dispatch — bisected into halves when the batch held several
        scenes (`retry_bisect`), so a single poison scene is isolated in
        O(log max_batch) rounds while its neighbours complete normally —
        and a request that has exhausted its `max_retries` re-dispatch
        budget completes with a typed `exec_failed` result.  The
        scheduler keeps serving either way.
        """
        self._c_failed_dispatches.inc()
        self._last_failure_t = time.monotonic()
        if self.overload is not None:
            self.overload.record_dispatch_failure(slot.cap)
        if self._tracer is not None:
            for r in slot.reqs:
                tid_owned = self._rid_trace.get(r.rid)
                if tid_owned is not None:
                    tid = tid_owned[0]
                    self._tracer.end_span(
                        tid, self._wspans.pop(r.rid, None),
                        t_end=self._last_failure_t, failed=True)
                    self._tracer.event(
                        tid, "dispatch_failed", t=self._last_failure_t,
                        dispatch_id=slot.dispatch_id, error=repr(exc))
        if self._recorder is not None:
            self._recorder.record(
                "dispatch_failed", dispatch_id=slot.dispatch_id,
                bucket=int(slot.cap), rids=[r.rid for r in slot.reqs],
                retries=slot.retries, error=repr(exc),
                instance=self.instance)
        retryable, dead = [], []
        for r in slot.reqs:
            a = self._attempts.get(r.rid, 0) + 1
            self._attempts[r.rid] = a
            (retryable if a <= self.max_retries else dead).append(r)
        for r in dead:
            self._attempts.pop(r.rid, None)
            self._outstanding[slot.cap] = \
                self._outstanding.get(slot.cap, 1) - 1
            self._complete_error_locked(
                r.rid, r.n_points, slot.cap, r.t_submit,
                ServeError(FLT.EXEC_FAILED,
                           f"micro-batch execution failed "
                           f"{self.max_retries + 1}x; last error: {exc}"))
        if not retryable:
            return
        self._backoff_locked(slot.retries)
        if len(retryable) > 1 and self.retry_bisect:
            mid = (len(retryable) + 1) // 2
            groups = (retryable[:mid], retryable[mid:])
        else:
            groups = (retryable,)
        for group in groups:
            self._dispatch(group, slot.cap, slot.retries + 1, "retry")

    def _backoff_locked(self, generation: int) -> None:
        """Jittered exponential backoff before a retry dispatch (the
        `retry_backoff_s` knob; 0 — the default — keeps retries
        immediate).  The retried requests live only on this call's
        stack, so the lock is safe to release for the wait: producers
        keep admitting scenes, and nothing can re-dispatch the failed
        slot's requests concurrently."""
        if self.retry_backoff_s <= 0 or self._closed:
            return
        delay = self.retry_backoff_s * (2 ** generation) \
            * (0.5 + self._rng.random())
        self._c_backoff.inc(delay)
        self._lock.release()
        try:
            time.sleep(delay)
        finally:
            self._lock.acquire()

    def _retire_oldest_locked(self, only_ready: bool = False) -> bool:
        """Retire the OLDEST in-flight micro-batch; returns False when
        there is nothing (eligible) to retire.

        FIFO retirement keeps completion order = dispatch order, like
        the synchronous scheduler — even when one bucket's depth
        overflow pays for older buckets' slots first (they were
        dispatched earlier, so waiting on them in order is the bound on
        total in-flight memory, not an accident).  The lock is RELEASED
        during the device wait so producer threads can keep admitting
        scenes; `_retiring` serializes retirers on the FIFO head.  With
        `only_ready` the call never blocks: it retires only a head whose
        result is already on host (poll()'s non-blocking tick).

        A wait that raises resolves the slot through the
        failure-isolation path (`_on_slot_failed`: retry / bisect /
        `exec_failed` results) — the slot is NOT re-queued, so one
        failed execution can never poison every later retire.  Only
        BaseExceptions that aren't Exceptions (KeyboardInterrupt,
        SystemExit) re-queue the slot and propagate.

        Caller must hold the lock exactly once (every public entry point
        acquires it with one `with self._lock:` and internal helpers
        never re-enter), so the release/re-acquire below fully drops it.
        """
        if only_ready and self._retiring:
            return False                # a blocking retirer owns the head
        while self._retiring:
            self._retire_cv.wait()
        if not self._inflight:
            return False
        if only_ready and not _is_ready(self._inflight[0].preds):
            return False
        slot = self._inflight.popleft()
        self._retiring = True
        self._lock.release()
        failure = None
        try:
            preds = np.asarray(self._wait_slot(slot))
        except Exception as e:
            failure = e
        except BaseException:
            self._lock.acquire()
            self._retiring = False
            # interpreter-level interrupt: put the slot back at the head
            # so its requests stay addressable, and propagate
            self._inflight.appendleft(slot)
            self._retire_cv.notify_all()
            raise
        self._lock.acquire()
        self._retiring = False
        self._retire_cv.notify_all()
        if failure is not None:
            self._on_slot_failed(slot, failure)
            return True                 # the slot WAS resolved
        t_done = time.monotonic()
        if self._last_failure_t is not None:
            self._g_recovery.set(t_done - self._last_failure_t)
            self._last_failure_t = None
        if self.overload is not None:
            self.overload.record_dispatch_success(slot.cap,
                                                  len(slot.reqs))
        tr = self._tracer
        for i, r in enumerate(slot.reqs):
            lat = t_done - r.t_submit
            self._attempts.pop(r.rid, None)
            self._outstanding[slot.cap] = \
                self._outstanding.get(slot.cap, 1) - 1
            self._completed.append(ServeResult(
                r.rid, preds[i, :r.n_points].astype(np.int32), r.n_points,
                slot.cap, 1.0 - r.n_valid / slot.cap, bool(slot.hits[i]),
                lat))
            self._h_latency.observe(lat)
            if tr is not None:
                tid_owned = self._rid_trace.pop(r.rid, None)
                if tid_owned is not None:
                    tid = tid_owned[0]
                    tr.end_span(tid, self._wspans.pop(r.rid, None),
                                t_end=t_done)
                    tr.event(tid, "retire", t=t_done,
                             dispatch_id=slot.dispatch_id)
                    self._await_handout(r.rid, tid_owned, t_done, "ok")
        if self._recorder is not None:
            self._recorder.record(
                "retire", dispatch_id=slot.dispatch_id,
                bucket=int(slot.cap), rids=[r.rid for r in slot.reqs],
                instance=self.instance)
        self._c_completed.inc(len(slot.reqs))
        self._c_ok.inc(len(slot.reqs))
        return True

    # -- failure completion / deadlines -----------------------------------

    def _complete_error_locked(self, rid: int, n_points: int, bucket: int,
                               t_submit: float, err: ServeError) -> None:
        """Terminate one request with a typed error result.

        The latency lands in the per-code error histogram — the average
        only ever covered OK results, so shed/timeout/exec_failed wait
        times used to vanish from telemetry entirely."""
        now = time.monotonic()
        lat = now - t_submit
        self._completed.append(ServeResult(
            rid, None, int(n_points), int(bucket), 0.0, False, lat, err))
        self._c_completed.inc()
        self._c_faults[err.code].inc()
        self._h_errlat[err.code].observe(lat)
        if self._tracer is not None:
            tid_owned = self._rid_trace.pop(rid, None)
            if tid_owned is not None:
                tid = tid_owned[0]
                self._tracer.end_span(tid, self._qspans.pop(rid, None),
                                      t_end=now)
                self._wspans.pop(rid, None)
                self._tracer.event(tid, "error", t=now, code=err.code,
                                   message=err.message)
                self._await_handout(rid, tid_owned, now, err.code)
        if self._recorder is not None:
            self._recorder.record("error", rid=rid, code=err.code,
                                  bucket=int(bucket),
                                  instance=self.instance)
            if err.code == FLT.EXEC_FAILED:
                self._recorder.dump("exec_failed",
                                    key=("exec_failed", self.instance, rid))

    def _await_handout(self, rid: int, tid_owned: tuple, t: float,
                       outcome: str) -> None:
        """Open a completed request's `result_wait` span (tracer wired
        in): it runs until poll/drain/take hands the result out."""
        sid = self._tracer.span(tid_owned[0], "result_wait", t_start=t)
        self._rspans[rid] = (*tid_owned, sid, outcome)

    def _handed_out(self, results) -> None:
        """Close the `result_wait` spans of the results being handed out
        and, for traces this scheduler began, their roots."""
        if self._tracer is not None and results:
            t = time.monotonic()
            for res in results:
                h = self._rspans.pop(res.rid, None)
                if h is None:
                    continue
                tid, owned, sid, outcome = h
                self._tracer.end_span(tid, sid, t_end=t)
                if owned:
                    self._tracer.end(tid, t=t, outcome=outcome)
        return results

    def _expire_overdue_locked(self) -> None:
        """Convert queued requests whose `deadline_s` elapsed into
        `timeout` results (a dispatched request runs to completion —
        device work cannot be cancelled)."""
        if not self._has_deadlines:
            return
        now = time.monotonic()
        live = 0
        for cap in list(self._queues):
            q = self._queues[cap]
            if any(r.deadline is not None for r in q):
                keep = deque()
                for r in q:
                    if r.deadline is not None and now >= r.deadline:
                        self._attempts.pop(r.rid, None)
                        self._outstanding[cap] = \
                            self._outstanding.get(cap, 1) - 1
                        self._complete_error_locked(
                            r.rid, r.n_points, cap, r.t_submit,
                            ServeError(
                                FLT.TIMEOUT,
                                f"deadline_s exceeded after "
                                f"{now - r.t_submit:.3f}s in queue",
                                retry_after_s=self.overload.retry_after(
                                    cap, self._outstanding.get(cap, 0))
                                if self.overload is not None else None))
                    else:
                        keep.append(r)
                self._queues[cap] = keep
            live += sum(1 for r in self._queues[cap]
                        if r.deadline is not None)
        self._has_deadlines = live > 0

    def _check_deadlines_locked(self, from_watchdog: bool = False) -> None:
        """Deadline policies: expire overdue requests (`deadline_s` ->
        `timeout` results), then the max_wait_s flush — a partial
        micro-batch executes once its oldest queued request exceeds the
        batching deadline.  A WATCHDOG-fired flush also snapshots the
        flight recorder: nobody was polling, so the ring around the
        stall is the evidence worth keeping.  The overload controller
        ticks here too (rate re-estimation + brownout ladder) — this
        sweep runs from submit()/poll() and the watchdog, so the
        control loop advances with traffic and on idle schedulers
        alike."""
        if self.overload is not None:
            self.overload.maybe_tick()
        self._expire_overdue_locked()
        if self.max_wait_s is None:
            return
        now = time.monotonic()
        for cap in list(self._queues):
            q = self._queues[cap]
            if q and now - q[0].t_submit >= self.max_wait_s:
                self._c_deadline_flushes.inc()
                if self._recorder is not None:
                    self._recorder.record(
                        "deadline_flush", bucket=int(cap),
                        queued=len(q), from_watchdog=from_watchdog,
                        instance=self.instance)
                    if from_watchdog:
                        self._recorder.dump(
                            "watchdog_deadline_flush",
                            key=("wd_flush", self.instance,
                                 int(self._c_deadline_flushes.value)))
                self._run_bucket(cap, "deadline")

    def _watchdog_tick(self) -> None:
        """Background completion (the `watchdog_s` Ticker): fire
        `max_wait_s` deadline flushes, expire per-request deadlines, and
        retire already-ready slots on an idle scheduler — so results
        complete without anyone calling poll(), and poll() itself stays
        constant-time."""
        with self._lock:
            if self._closed:
                return
            self._check_deadlines_locked(from_watchdog=True)
            while self._retire_oldest_locked(only_ready=True):
                pass
            if self._pump_locked():
                while self._retire_oldest_locked(only_ready=True):
                    pass

    # -- telemetry --------------------------------------------------------

    def service_rate(self, cap: int) -> float | None:
        """Observed EWMA service rate (scenes/s) for one bucket — None
        without an overload controller or before it has an estimate."""
        with self._lock:
            return self.overload.service_rate(cap) \
                if self.overload is not None else None

    def retry_after_hint(self) -> float | None:
        """Aggregate backpressure hint: estimated seconds until this
        scheduler's outstanding work drains at the observed completion
        rate (what a router aggregates across workers for a pool-level
        shed).  None without an overload controller."""
        with self._lock:
            return self.overload.retry_after_hint() \
                if self.overload is not None else None

    def stats(self) -> dict:
        """Serving telemetry: padding overhead, mapping + assembly cache
        hit rates, assembly time, per-bucket occupancy, deadline flushes,
        pipeline state, compile counts, latency, and the fault counters
        (rejected / shed / timeout / exec_failed, failed dispatches,
        retries, last failure->recovery time).  `scheduler_max_backlog`
        is the PER-BUCKET admission bound (the router's per-worker bound
        surfaces as `router_max_backlog` in ITS stats())."""
        with self._lock:
            buckets = {}
            for cap, (m_scenes, m_batches, m_dummies) in \
                    self._m_buckets.items():
                issued = m_scenes.value + m_dummies.value
                buckets[int(cap)] = {
                    "scenes": m_scenes.value,
                    "batches": m_batches.value,
                    "dummy_scenes": m_dummies.value,
                    "occupancy": (m_scenes.value / issued
                                  if issued else 0.0),
                    "max_batch": self.max_batch_for(cap),
                }
            real_points = self._c_points_real.value
            overhead = (self._c_rows_issued.value / real_points - 1.0) \
                if real_points else 0.0
            n_batches = self._h_assembly.count
            assembly_s = self._h_assembly.sum
            h_lat = self._h_latency
            return {
                "n_submitted": self._c_submitted.value,
                "n_completed": self._c_completed.value,
                "n_ok": self._c_ok.value,
                "queue_depth": sum(len(q) for q in self._queues.values()),
                "in_flight": len(self._inflight),
                "padding_overhead": overhead,
                "mapping_cache": self.engine.cache_stats(),
                "assembly_cache": (self.assembly_cache.stats()
                                   if self.assembly_cache else None),
                "assembly_time_s": assembly_s,
                "assembly_time_per_batch_s": (assembly_s / n_batches
                                              if n_batches else 0.0),
                "deadline_flushes": self._c_deadline_flushes.value,
                "buckets": buckets,
                "max_batch": self.max_batch,
                "max_batch_overrides": dict(self.max_batch_overrides),
                "scheduler_max_backlog": self.max_backlog,
                "pipeline_depth": self.pipeline_depth,
                "n_devices": (int(np.prod(list(self.mesh.shape.values())))
                              if self.mesh is not None else 1),
                "compiles": {
                    "build": _jit_cache_size(self.engine._build),
                    "apply_batch": _jit_cache_size(self._apply),
                },
                "latency_avg_s": (h_lat.sum / h_lat.count
                                  if h_lat.count else 0.0),
                "latency_quantiles_s": h_lat.quantiles(),
                "faults": {
                    **{c: m.value for c, m in self._c_faults.items()},
                    "failed_dispatches": self._c_failed_dispatches.value,
                    "retries": self._c_retries.value,
                    "retry_backoff_s": float(self._c_backoff.value),
                    "recovery_s": self._g_recovery.value,
                },
                "watchdog": self._watchdog is not None,
                "closed": self._closed,
            }
