"""The load generator: one general loop that a traffic file steers.

A traffic file (`bench/traffic/<mix>.json`) holds only parameters:

  loop          "open": requests sent at the due times of `arrivals`,
                whatever the server does.
  arrivals      {"kind": "poisson", "rate_per_s": r}: round(r * seconds)
                due times drawn uniformly over the window (a Poisson
                process given its count).
  schedule_seed the due times and the order of each block of `pool`
                requests are drawn from it, the same in every run, so
                that runs with different `--seed`s (scenes, transforms,
                features) offer the same timeline and the same work.
  scene_voxels  [lo, hi]: valid voxels per base scene, spread evenly.
  pool          number of base scenes made at set-up.
  serving       ServeScheduler keyword arguments the mix needs.
  warm_batch_sizes  real scenes per micro-batch to warm up, per bucket.
  await_s       seconds past the window's close to wait for the
                requests due in it.
  sample        served scenes compared with the reference per run.

The generator calls only `submit` and `poll` of the scheduler and times
each request from when it was due to when `poll` hands its result back.
Each call into the server runs under a `jax.profiler.TraceAnnotation`,
so a traced run can say what the host was doing during a device gap.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

POLL_S = 0.001


def due_times(arrivals: dict, seed: int, seconds: float) -> np.ndarray:
    """Sorted due times in [0, seconds) of an open loop's requests."""
    rate = float(arrivals["rate_per_s"])
    n = int(round(rate * seconds))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7004]))
    if arrivals["kind"] != "poisson":
        raise ValueError(f"unknown arrivals kind {arrivals['kind']!r}")
    return np.sort(rng.uniform(0.0, seconds, n))


@dataclasses.dataclass
class Request:
    r: int                  # request number in the stream
    base: int               # base scene index
    rid: int                # scheduler request id
    due: float              # monotonic time it was due
    sent: float             # monotonic time submit was called
    done: float | None = None
    ok: bool | None = None
    preds: np.ndarray | None = None
    error: str | None = None


class Window:
    """Everything one measured window produced."""

    def __init__(self, t0: float, seconds: float):
        self.t0 = t0
        self.t1 = t0 + seconds
        self.requests: dict[int, Request] = {}      # by rid

    def in_window(self, t: float | None) -> bool:
        return t is not None and self.t0 <= t < self.t1

    def due_in_window(self) -> list[Request]:
        return [q for q in self.requests.values() if self.in_window(q.due)]


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _send(sched, stream, win: Window, r: int, due: float) -> Request:
    base, coords, feats = stream.request(r)
    sent = time.monotonic()
    with _annotate("bench.submit"):
        rid = sched.submit(coords, feats)
    q = Request(r, base, rid, due, sent)
    win.requests[rid] = q
    return q


def collect(sched, win: Window) -> list[Request]:
    with _annotate("bench.poll"):
        results = sched.poll()
    t = time.monotonic()
    done = []
    for res in results:
        q = win.requests.get(res.rid)
        if q is None:
            continue
        q.done, q.ok = t, res.ok
        if res.ok:
            q.preds = np.asarray(res.preds)
        else:
            q.error = str(res.error)
        done.append(q)
    return done


def _idle(until: float) -> None:
    wait = min(POLL_S, until - time.monotonic())
    if wait > 0:
        with _annotate("bench.idle"):
            time.sleep(wait)


def _nothing():
    pass


def open_loop(sched, stream, due: np.ndarray, seconds: float,
              await_s: float, on_open=_nothing, on_close=_nothing) -> Window:
    """Open the window (`on_open()`) and send request i at window start +
    due[i]; after the window closes (`on_close()`), wait up to `await_s`
    for every request due in it."""
    on_open()
    win = Window(time.monotonic(), seconds)
    due_abs = win.t0 + due
    i = 0
    while time.monotonic() < win.t1:
        while i < len(due_abs) and due_abs[i] <= time.monotonic():
            _send(sched, stream, win, i, float(due_abs[i]))
            i += 1
        collect(sched, win)
        nxt = due_abs[i] if i < len(due_abs) else win.t1
        _idle(min(nxt, win.t1))
    on_close()
    while i < len(due_abs):             # due in the window, sent late
        _send(sched, stream, win, i, float(due_abs[i]))
        i += 1
    deadline = win.t1 + await_s
    while time.monotonic() < deadline and \
            any(q.done is None for q in win.requests.values()):
        collect(sched, win)
        _idle(deadline)
    return win


def latency_ms(win: Window) -> list[float]:
    """Due -> result of every request due in the window, in ms; a
    request that failed or never came back is +inf."""
    out = []
    for q in win.due_in_window():
        out.append((q.done - q.due) * 1e3 if q.ok else math.inf)
    return out


def quantile(values, q: float) -> float:
    """The q-quantile by the nearest-rank rule (inf-safe)."""
    v = sorted(values)
    if not v:
        raise ValueError("no values")
    k = max(0, math.ceil(q * len(v)) - 1)
    return float(v[k])
