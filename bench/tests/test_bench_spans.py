"""CPU tests of `bench/spans.py`, the span-tree decomposition of a traced
window: the clock anchors, each chip's idle stretches, the readings of
the scheduler's span trees and the charging of idle gaps, on a hand-made
two-chip trace and hand-made spans; and one traced window at a tiny
size."""

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import spans as SP  # noqa: E402
from repro.obs import SpanTracer  # noqa: E402

# Times in ns on the trace's clock.  Window [1000, 11000); obs.clock
# anchors at 500 and 20000; chip 0 runs [1000, 4000) and [6000, 8000),
# so it idles in [4000, 6000) and [8000, 11000); chip 1 is never idle;
# the benchmark sleeps (bench.idle) in [9000, 10000).
TRACE = """
planes {
  id: 1
  name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 10000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 10000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "obs.clock" } }
  event_metadata { key: 3 value { id: 3 name: "bench.idle" } }
}
planes {
  id: 2
  name: "/device:TPU:0"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 21 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 21 offset_ps: 6000000 duration_ps: 2000000 }
  }
  event_metadata { key: 21 value { id: 21 name: "fusion.1" } }
}
planes {
  id: 3
  name: "/device:TPU:1"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 21 offset_ps: 0 duration_ps: 20000000 }
  }
  event_metadata { key: 21 value { id: 21 name: "fusion.1" } }
}
"""
# The tracer's clock reads 99,500 ns more than the trace's.
OFFSET = 99_500
ANCHORS = [("obs.clock", 100_000 - 10, 100_000 + 10),
           ("obs.clock", 119_500 - 6, 119_500 + 6)]


def mono(ns: float) -> float:
    """Tracer seconds of a trace time."""
    return (ns + OFFSET) * 1e-9


def _request(tr, tid, spans):
    """A closed request tree from [(name, start, end, parent, attrs)] in
    trace ns; the root spans the first start to the last end."""
    tr.begin(tid, t=mono(min(s[1] for s in spans)), rid=tid)
    ids = {}
    for name, a, b, parent, attrs in spans:
        ids[name] = tr.span(tid, name, parent=ids.get(parent),
                            t_start=mono(a), t_end=mono(b), **attrs)
    tr.end(tid, t=mono(max(s[2] for s in spans)), outcome="ok")
    return tr.get(tid)


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(TRACE)


@pytest.fixture(scope="module")
def traces():
    tr = SpanTracer()
    batch = {"dispatch_id": 1}
    disp = {"dispatch_id": 1, "trigger": "deadline",
            "thread": "serve-watchdog"}
    a = _request(tr, "a", [
        ("admission", 1500, 2000, None, {}),
        ("lock_wait", 2000, 4000, None, {}),
        ("queue_wait", 4000, 4500, None, {}),
        ("dispatch", 4500, 7000, None, disp),
        ("assembly", 4500, 6800, "dispatch", {}),
        ("mapping", 4600, 6500, "assembly", {"cache_hit": False}),
        ("stack_levels", 6500, 6800, "assembly", batch),
        ("launch", 6800, 7000, "dispatch", {}),
        ("device_wait", 7000, 9000, None, {}),
        ("retire", 9000, 9000, None, {}),
        ("result_wait", 9000, 9400, None, {})])
    # b shares a's micro-batch; its scene's pyramid came from the cache,
    # and nothing covers [4300, 4500) of its root
    b = _request(tr, "b", [
        ("admission", 4000, 4100, None, {}),
        ("lock_wait", 4100, 4300, None, {}),
        ("dispatch", 4500, 7000, None, disp),
        ("assembly", 4500, 6800, "dispatch", {}),
        ("mapping", 4550, 4600, "assembly", {"cache_hit": True}),
        ("stack_levels", 6500, 6800, "assembly", batch),
        ("launch", 6800, 7000, "dispatch", {}),
        ("device_wait", 7000, 9000, None, {}),
        ("result_wait", 9000, 9000, None, {})])
    return [a, b]


@pytest.fixture(scope="module")
def clk(profile):
    return SP.clock(profile, ANCHORS)


def test_clock_maps_by_the_first_anchor_and_logs_the_drift(profile,
                                                           capsys):
    clk = SP.clock(profile, ANCHORS)
    assert clk.offset_ns == pytest.approx(-OFFSET)
    assert clk.ns(mono(4321)) == pytest.approx(4321)
    assert clk.drift_ns == pytest.approx(0)
    shifted = ANCHORS[:1] + [("obs.clock", 119_000, 119_010)]
    assert SP.clock(profile, shifted).drift_ns == pytest.approx(495)
    assert "drift_ns=495.0" in capsys.readouterr().err
    # the tracer anchored nothing, or the trace lost an anchor: no mapping
    assert SP.clock(profile, []) is None
    assert SP.clock(profile, ANCHORS[:1]) is None


def test_device_idle_per_chip(profile):
    dev = SP.device_idle(profile)
    assert (dev["t0"], dev["t1"]) == (1000, 11000)
    assert dev["idle"] == [[(4000, 6000), (8000, 11000)], []]
    assert SP.device_idle(profile, seconds=4e-6)["idle"][0] == \
        [(4000, 5000)]
    assert SP.merged([(5, 9), (0, 2), (1, 3), (3, 3)]) == [(0, 3), (5, 9)]
    assert SP.overlap_ns([(0, 3), (5, 9)], [(2, 6), (8, 20)]) == 3


def test_span_medians(traces, clk, profile):
    got = SP.readings(traces, clk, SP.device_idle(profile))
    # lock_wait: 2000 and 200 ns
    assert got["lock_wait_ms"] == pytest.approx(1.1e-3)
    # only the scene that missed the mapping cache
    assert got["mapping_wall_ms"] == pytest.approx(1.9e-3)
    # one stacking per micro-batch, though both trees record it
    assert got["stack_ms"] == pytest.approx(3e-4)
    assert got["device_wait_ms"] == pytest.approx(2e-3)
    assert got["result_wait_ms"] == pytest.approx(2e-4)


def test_idle_host_share(traces, clk, profile):
    # chip 0 idles under work spans in [4000, 4100) (b's admission) and
    # [4550, 6000) (b's mapping, then a's); chip 1 never idles:
    # (1550 + 0) / 2 of a 10,000 ns window
    got = SP.readings(traces, clk, SP.device_idle(profile))
    assert got["idle_host_share"] == pytest.approx(7.75)


def test_readings_are_none_without_spans(profile, clk):
    dev = SP.device_idle(profile)
    assert SP.readings([], clk, dev) == dict.fromkeys(
        ("lock_wait_ms", "mapping_wall_ms", "stack_ms", "device_wait_ms",
         "result_wait_ms", "idle_host_share"))
    assert SP.idle_host_share([], clk, None) is None


def test_idle_host_share_needs_the_clock(traces, profile):
    assert SP.idle_host_share(traces, None, SP.device_idle(profile)) is None


def test_uncovered_share(traces):
    a, b = traces
    assert SP.uncovered_share(a) == pytest.approx(0.0)
    # b's root [4000, 9000): [4300, 4500) uncovered
    assert SP.uncovered_share(b) == pytest.approx(200 / 5000)


def test_dispatches_count_micro_batches_once(traces):
    assert SP.dispatches(traces) == {"deadline on serve-watchdog": 1}


def test_idle_gaps_are_charged_to_program_spans(traces, clk, profile):
    dev = SP.device_idle(profile)
    gaps = SP.charged_gaps(dev, clk, traces)
    # [8000, 11000): no span open at 9500, the benchmark sleeps;
    # [4000, 6000): the mapping pass, a work span, at 5000, though
    # dispatch and assembly are open too and lock_wait ends at 4000
    assert gaps == [["bench.idle", pytest.approx(3e-6)],
                    ["mapping [deadline on serve-watchdog]",
                     pytest.approx(2e-6)]]
    # without a clock, the benchmark's own labels
    assert [g[0] for g in SP.charged_gaps(dev, None, traces)] == \
        ["bench.idle", "no bench call"]


# -- one traced window at a tiny size -------------------------------------

SERVED = json.loads(
    (ROOT / "bench" / "configs" / "mini-minkunet-kitti.json").read_text())
TINY_CFG = {
    "name": "tiny-unet", "source": "test", "model": "minkunet", "c_in": 4,
    "n_classes": 5, "stem": 8, "enc_planes": [8], "dec_planes": [8],
    "blocks_per_stage": 1, "ln_eps": 1e-6, "weights_seed": 3,
    "dtype": "float32", "matmul_precision": "default",
    "correct": SERVED["correct"], "reduced": [], "assumed": []}
TINY_MIX = {"loop": "open", "arrivals": {"kind": "poisson", "rate_per_s": 8},
            "schedule_seed": 5, "scene_voxels": [150, 250], "pool": 4,
            "serving": {"max_wait_s": 0.05},
            "warm_batch_sizes": [1, 2, 3, 4], "await_s": 30, "sample": 3}
CELL = "tiny-unet.tinymix"


def test_tiny_traced_window_reads_the_span_trees(tmp_path, capsys,
                                                monkeypatch):
    """A copy of the benchmark plus a tiny configuration, mix and cell:
    one traced window on the CPU anchors the clock twice, marks the
    window in the trace and reads every span of the decomposition; the
    CPU trace has no chip, so the idle share reads nothing."""
    seen = []
    device_idle = SP.device_idle

    def spy(profile, seconds=None):
        seen.append(SP.host_events(profile, "bench.window"))
        return device_idle(profile, seconds)

    monkeypatch.setattr(SP, "device_idle", spy)
    root = tmp_path / "tree"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(ROOT / "src")
    (root / "bench" / "configs" / "tiny-unet.json").write_text(
        json.dumps(TINY_CFG))
    (root / "bench" / "traffic" / "tinymix.json").write_text(
        json.dumps(TINY_MIX))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-unet", "source": "test",
                            "file": "bench/configs/tiny-unet.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-unet",
                              "traffic": "tinymix", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = SP.run(CELL, 2**31 + 14, 1.0, root=root, on_chip=False)
    assert out["failed"] == 0 and out["requests"] > 0, out
    got = out["readings"]
    for name in ("lock_wait_ms", "stack_ms", "device_wait_ms",
                 "result_wait_ms"):
        assert got[name] >= 0.0, (name, got)
    assert got["mapping_wall_ms"] > 0.0
    assert got["idle_host_share"] is None
    assert out["uncovered_share"]["max"] <= 0.01, out["uncovered_share"]
    assert sum(out["dispatches"].values()) > 0
    assert out["clock"] is not None and out["latency_p50_ms"] > 0
    assert "clock anchors=2" in capsys.readouterr().err
    assert len(seen) == 1 and len(seen[0]) == 1, seen
