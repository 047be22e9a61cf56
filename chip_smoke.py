#!/usr/bin/env python3
"""Smoke run of the MinkUNet served path on the chip.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # scene-sharded scheduler, four chips

The one-chip run does, in one process:

  1. refuse a host whose first JAX device is not a TPU (no CPU branch);
  2. serve full-width MinkUNet (`minkunet_init` defaults, 4 input channels,
     19 classes, random weights from SEED) through `PointCloudEngine` +
     `ServeScheduler` with `mesh=None`: N_SCENES seeded synthetic LiDAR
     scenes of 34,000 or 60,000 voxels, every row a valid voxel, which the
     server pads into its 65,536-row bucket, at max_batch 2.  Every result
     must be ok; the first error aborts the run;
  3. check each served scene against an unbatched `minkunet_apply(
     flow="gms")` of the same scene on the same chip, at the scene's own
     row count: the reference sees no padding and no masked row;
  4. run `flow="pallas_fused"` and `flow="pallas"` on one scene of the
     bucket, check that their compiled HLO holds `tpu_custom_call` (Mosaic
     kernels, not the interpreter), and compare each with `flow="fod"`.

`--four-chips` runs only the scene-sharded path (`shard_over_scenes` over a
mesh of four devices) and the one-device per-scene reference it is held to.

Agreement rule: a served class id passes where its reference logit is
within RTOL * max|logit| of the row's best, so float rounding may swap
near-tied classes but a wrong kernel map or a padding leak may not; flow
logits must agree within RTOL * max|logit|.  Progress and per-phase numbers
go to stdout as `chip_smoke: ...` lines; the last line is one JSON object
`{"ok": true, "device": {...}}`, printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_CLASSES = 19
C_IN = 4
N_SCENES = 8
SEED = 0
# Valid voxels of scene i: SCENE_VOXELS[i % 2].  Both sizes fall in the
# 65,536-row bucket; two sizes keep the unpadded reference at two compiles.
SCENE_VOXELS = (60_000, 34_000)
MAX_BATCH = 2
RTOL = 2e-2
PALLAS_FLOWS = ("pallas_fused", "pallas")


class SmokeError(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


class CompileClock:
    """Backend-compile seconds and count, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0

        def on_event(event, duration_secs, **_):
            if event == self.EVENT:
                self.seconds += duration_secs
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def lap(self):
        """(seconds, count) since the previous lap."""
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


def require_tpu(n_devices: int):
    """The first `n_devices` TPU devices; SmokeError on any other host."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeError(f"no TPU: JAX's first device is {devs[0]} "
                         f"(platform {devs[0].platform!r})")
    if len(devs) < n_devices:
        raise SmokeError(f"need {n_devices} TPU devices, JAX sees "
                         f"{len(devs)}")
    return devs[:n_devices]


def make_scenes(n_scenes: int = N_SCENES, sizes=SCENE_VOXELS,
                seed: int = SEED):
    """Seeded LiDAR-like scenes (coords, mask, feats) with no padding:
    scene i has sizes[i % len(sizes)] rows, every one a valid voxel."""
    from repro.data.synthetic import city_scene
    scenes = []
    for i in range(n_scenes):
        n = sizes[i % len(sizes)]
        # city_scene yields ~0.95x its ask in unique voxels, valid rows first
        c, m, f = city_scene(seed * 1000 + i, n + n // 4)
        if m.sum() < n:
            raise SmokeError(f"scene {i}: {int(m.sum())} voxels, need {n}")
        scenes.append((c[:n], m[:n], f[:n]))
    return scenes


def init_params(seed: int = SEED, **widths):
    """MinkUNet at `minkunet_init`'s published widths unless overridden."""
    import jax
    from repro.models import minkunet as MU
    return MU.minkunet_init(jax.random.key(seed), c_in=C_IN,
                            n_classes=N_CLASSES, **widths)


def serve(params, scenes, *, max_batch: int = MAX_BATCH, mesh=None):
    """Serve `scenes` through the scheduler; class ids per scene in order.

    SmokeError names the first result that is not ok."""
    from repro.serve.engine import PointCloudEngine
    from repro.serve.scheduler import ServeScheduler
    engine = PointCloudEngine(params, n_stages=len(params["enc"]),
                              flow="fod", max_batch=max_batch, mesh=mesh)
    sched = ServeScheduler(engine, max_batch=max_batch, mesh=mesh)
    try:
        rids = [sched.submit(c, f, m) for c, m, f in scenes]
        sched.flush()
        by_rid = {r.rid: r for r in sched.drain()}
    finally:
        sched.close()
    preds = []
    for i, rid in enumerate(rids):
        r = by_rid.get(rid)
        if r is None:
            raise SmokeError(f"scene {i}: no result for request {rid}")
        if not r.ok:
            raise SmokeError(f"scene {i}: request {rid} failed: {r.error}")
        preds.append(np.asarray(r.preds))
    return preds, engine, sched.stats()


def reference_logits(params, scenes, device=None):
    """Per-scene, unbatched `minkunet_apply(flow="gms")` logits, each at
    the scene's own row count (one compile per distinct count)."""
    import jax
    import jax.numpy as jnp
    from repro.core import mapping as M
    from repro.models import minkunet as MU

    @jax.jit
    def ref(params, c, m, f):
        return MU.minkunet_apply(params, M.make_point_cloud(c, m), f,
                                 flow="gms")

    params = jax.device_put(params, device)
    out = []
    for c, m, f in scenes:
        args = [jax.device_put(jnp.asarray(a), device) for a in (c, m, f)]
        out.append(np.asarray(ref(params, *args)))
    return out


def check_preds(preds, ref_logits, mask, rtol: float = RTOL) -> dict:
    """Served class ids vs reference logits on the valid rows (see the
    module's agreement rule)."""
    ref = np.asarray(ref_logits, np.float64)[mask]
    p = np.asarray(preds)[mask]
    scale = max(float(np.abs(ref).max()), 1e-30)
    gap = ref.max(-1) - np.take_along_axis(ref, p[:, None], -1)[:, 0]
    return {"rows": int(mask.sum()),
            "agree": float(np.mean(p == ref.argmax(-1))),
            "max_gap_rel": float(gap.max() / scale),
            "ok": bool(gap.max() <= rtol * scale)}


def check_served(preds, refs, scenes, rtol: float = RTOL):
    """check_preds for every scene; SmokeError on the first failure."""
    reports = []
    for i, (p, ref, (_, m, _)) in enumerate(zip(preds, refs, scenes)):
        if p.shape != m.shape:
            raise SmokeError(f"scene {i}: {p.shape} class ids for "
                             f"{m.shape[0]} rows")
        rep = check_preds(p, ref, m, rtol)
        log(f"scene {i}: rows={rep['rows']} agree={rep['agree']!r} "
            f"max_gap_rel={rep['max_gap_rel']!r} tol={rtol!r}")
        if not rep["ok"]:
            raise SmokeError(f"scene {i} disagrees with the reference: "
                             f"{rep}")
        reports.append(rep)
    return reports


def flow_logits(params, engine, scene, flows):
    """Logits of one scene padded to its bucket, per flow, on the served
    mapping pyramid; whether each compiled program holds a Mosaic kernel
    call; and the seconds of one warm forward per flow.  Returns
    ({flow: logits}, {flow: bool}, {flow: seconds}, mask)."""
    import jax
    import jax.numpy as jnp
    from repro.core import mapping as M
    from repro.models import minkunet as MU
    from repro.serve import buckets as BK

    coords, mask, feats = scene
    cap = engine.ladder.bucket_for(coords.shape[0])
    c, m, f = (jnp.asarray(a) for a in BK.pad_scene(coords, mask, feats,
                                                     cap))
    levels, _ = engine.levels_for(coords, mask)
    logits, kernels, seconds = {}, {}, {}
    for flow in flows:
        def apply(params, levels, c, m, f, flow=flow):
            return MU.minkunet_apply(params, M.PointCloud(c, m, 1), f,
                                     flow=flow, levels=levels)
        compiled = jax.jit(apply).lower(params, levels, c, m, f).compile()
        kernels[flow] = "tpu_custom_call" in compiled.as_text()
        logits[flow] = np.asarray(compiled(params, levels, c, m, f))
        t = time.perf_counter()
        jax.block_until_ready(compiled(params, levels, c, m, f))
        seconds[flow] = time.perf_counter() - t
    return logits, kernels, seconds, np.asarray(m)


def rel_err(a, b, mask) -> float:
    """max |a - b| over valid rows, relative to max |b|."""
    a, b = np.asarray(a)[mask], np.asarray(b)[mask]
    return float(np.abs(a - b).max() / max(float(np.abs(b).max()), 1e-30))


def _n_batches(stats) -> int:
    return sum(b["batches"] for b in stats["buckets"].values())


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_one_chip(clock) -> list:
    """The one-chip phases; returns the devices they ran on."""
    devs = require_tpu(1)
    (dev,) = devs
    scenes = make_scenes()
    params = init_params()
    log(f"{len(scenes)} scenes, valid voxels "
        f"{[int(m.sum()) for _, m, _ in scenes]}, no masked rows")

    t = time.perf_counter()
    preds, engine, stats = serve(params, scenes, max_batch=MAX_BATCH)
    wall = time.perf_counter() - t
    comp, n = clock.lap()
    log(f"serve: wall_s={wall!r} compile_s={comp!r} compiles={n} "
        f"batches={_n_batches(stats)} completed={stats['n_completed']}")

    t = time.perf_counter()
    refs = reference_logits(params, scenes, dev)
    wall = time.perf_counter() - t
    comp, n = clock.lap()
    log(f"reference gms: wall_s={wall!r} compile_s={comp!r} compiles={n}")
    check_served(preds, refs, scenes)

    t = time.perf_counter()
    logits, kernels, seconds, mask = flow_logits(
        params, engine, scenes[0], ("fod",) + PALLAS_FLOWS)
    wall = time.perf_counter() - t
    comp, n = clock.lap()
    log(f"flows: wall_s={wall!r} compile_s={comp!r} compiles={n} "
        f"fod_forward_s={seconds['fod']!r}")
    for flow in PALLAS_FLOWS:
        err = rel_err(logits[flow], logits["fod"], mask)
        log(f"{flow}: tpu_custom_call={kernels[flow]} "
            f"forward_s={seconds[flow]!r} rel_err_vs_fod={err!r} "
            f"tol={RTOL!r}")
        if not kernels[flow]:
            raise SmokeError(f"{flow}: no tpu_custom_call in the compiled "
                             "program (kernel not compiled by Mosaic)")
        if not err <= RTOL:
            raise SmokeError(f"{flow} disagrees with fod: {err!r}")
    log(f"peak_bytes_in_use={_peak_bytes(dev)}")
    return devs


def run_four_chips(clock) -> list:
    """The scene-sharded phase on four devices; returns them."""
    from repro.distributed import sharding as SH
    devs = require_tpu(4)
    scenes = make_scenes()
    params = init_params()
    mesh = SH.make_scene_mesh(devices=devs)

    t = time.perf_counter()
    preds, _, stats = serve(params, scenes, max_batch=4, mesh=mesh)
    wall = time.perf_counter() - t
    comp, n = clock.lap()
    log(f"sharded serve: devices={stats.get('n_devices')} wall_s={wall!r} "
        f"compile_s={comp!r} compiles={n} batches={_n_batches(stats)}")
    if stats.get("n_devices") != 4:
        raise SmokeError(f"scheduler ran on {stats.get('n_devices')} "
                         "devices, not 4")

    t = time.perf_counter()
    refs = reference_logits(params, scenes, devs[0])
    wall = time.perf_counter() - t
    comp, n = clock.lap()
    log(f"one-device reference: wall_s={wall!r} compile_s={comp!r} "
        f"compiles={n}")
    check_served(preds, refs, scenes)
    log(f"peak_bytes_in_use={[_peak_bytes(d) for d in devs]}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the scene-sharded path on 4 chips")
    args = ap.parse_args(argv)
    try:
        from repro.compile_cache import enable_compile_cache
        require_tpu(1)
        log(f"compile cache: {enable_compile_cache()}")
        clock = CompileClock()
        devs = (run_four_chips if args.four_chips else run_one_chip)(clock)
    except Exception as e:  # noqa: BLE001 — every failure is a nonzero exit
        print(f"chip_smoke: FAIL: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
