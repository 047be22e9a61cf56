"""CPU tests of the profiler-trace reduction (`bench/trace_reduce.py`)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as TR  # noqa: E402

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def small():
    from jax.profiler import ProfileData
    return TR.reduce_trace(ProfileData.from_text_proto(
        (HERE / "trace_small.pbtxt").read_text()))


def test_busy_is_the_union_of_op_intervals(small):
    assert small["window_s"] == pytest.approx(10e-6)
    # chip 0: ops [1, 3) and [2, 5) overlap, then [7, 8): 5 us busy;
    # chip 1: one op over the whole window, clipped to it
    assert small["busy_s"] == pytest.approx([5e-6, 10e-6])


def test_module_time_is_clipped_to_the_window(small):
    assert small["modules"]["build_one"] == pytest.approx(
        {"runs": 1, "seconds": 4e-6})
    # apply_one runs [7, 13) us: 4 us of it fall inside the window
    assert small["modules"]["apply_one"] == pytest.approx(
        {"runs": 1, "seconds": 4e-6})


def test_idle_gaps_are_labelled_by_the_host_call(small):
    # chip 0 idles in [5, 7) (bench.submit, then bench.idle at the
    # midpoint 6) and in [8, 11) (no bench call)
    assert small["idle_gaps"] == [["no bench call", pytest.approx(3e-6)],
                                  ["bench.idle", pytest.approx(2e-6)]]


def test_device_ops_are_named_by_module_and_op(small):
    ops = dict(small["device_ops"])
    assert ops == pytest.approx({"build_one/fusion.1": 2e-6,
                                 "build_one/sort.2": 3e-6,
                                 "apply_one/fusion.1": 1e-6,
                                 "fusion.1": 10e-6})


def test_window_length_can_be_given():
    from jax.profiler import ProfileData
    red = TR.reduce_trace(ProfileData.from_text_proto(
        (HERE / "trace_small.pbtxt").read_text()), seconds=4e-6)
    # window [1, 5) us: chip 0 busy over all of it, build_one only
    assert red["window_s"] == pytest.approx(4e-6)
    assert red["busy_s"] == pytest.approx([4e-6, 4e-6])
    assert set(red["modules"]) == {"build_one", "apply_one"}
    assert red["modules"]["apply_one"]["runs"] == 0


def test_union_and_gaps_on_unsorted_input():
    iv = [(5, 9), (0, 2), (1, 3), (8, 10)]
    assert TR.union_seconds(iv, 0, 10) == pytest.approx(8e-9)
    assert TR.gaps(iv, 0, 12) == [(3, 5), (10, 12)]
    assert TR.module_name("jit_apply_one(123)") == "apply_one"
    assert TR.op_name("%while.17 = (s32[]) while(%tuple.3)") == "while.17"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    return TR.reduce_trace(ProfileData.from_text_proto(
        (HERE / "trace_recorded.pbtxt").read_text()))


def test_recorded_trace_modules_and_nested_ops(recorded):
    assert recorded["window_s"] == pytest.approx(0.171400432)
    # four scenes mapped, one micro-batch applied
    assert recorded["modules"]["build_one"]["runs"] == 4
    assert recorded["modules"]["build_one"]["seconds"] == pytest.approx(
        (16609053 + 16608206 + 16608098 + 16608531) * 1e-9)
    assert recorded["modules"]["apply_one"] == pytest.approx(
        {"runs": 1, "seconds": 13753728e-9})
    # a while op spans the ops of its body: busy is their union (7.51 ms),
    # not the sum of the op durations (12.19 ms)
    assert recorded["busy_s"] == pytest.approx([7512277e-9])
    assert all(name.startswith("build_one/")
               for name, _ in recorded["device_ops"])
