"""CPU tests of the benchmark harness (`bench/run.py`).

The harness's look for a TPU is skipped with `on_chip=False`; everything
else of a run is driven as the chip would drive it, at a tiny size, in a
copy of the benchmark to which the test adds a configuration, a traffic
mix, a cell and a metric reader as new files.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

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
NEW_METRIC = '''"""Requests due in the window that came back ok (a metric added as a
file)."""


def read(ctx):
    return float(sum(1 for q in ctx.window.due_in_window() if q.ok)) or None
'''
CELL = "tiny-unet.tinymix"
SERVED_CELL = "mini-minkunet-kitti.stream"


def _run_cli(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SERVED_CELL,
         "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=120)


def _json_lines(text: str) -> list:
    return [ln for ln in text.splitlines() if ln.startswith("{")]


def test_refuses_a_host_without_tpu(capsys):
    from bench import run as R
    rc = R.main(["--workload", SERVED_CELL, "--seed", "3",
                 "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert not _json_lines(out.out)
    assert "no TPU" in out.err


def test_refuses_to_run_from_the_benchmark_files_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert not _json_lines(proc.stdout)


@pytest.fixture(scope="module")
def tree(tmp_path_factory) -> Path:
    """A copy of the benchmark plus one configuration, one mix, one cell
    and one metric, each added as a file; the harness is not edited."""
    root = tmp_path_factory.mktemp("bench_tree")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(ROOT / "src")
    (root / "bench" / "configs" / "tiny-unet.json").write_text(
        json.dumps(TINY_CFG))
    (root / "bench" / "traffic" / "tinymix.json").write_text(
        json.dumps(TINY_MIX))
    (root / "bench" / "metrics" / "scenes_done.tinymix.py").write_text(
        NEW_METRIC)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-unet", "source": "test",
                            "file": "bench/configs/tiny-unet.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": CELL, "config": "tiny-unet",
                              "traffic": "tinymix", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "latency_p50_ms":
            m["workloads"].append(CELL)
    spec["per_layer"].append({
        "name": "scenes_done.tinymix", "unit": "scenes", "better": "higher",
        "source": "host_clock", "layer": "load generator",
        "moves": "latency_p50_ms", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_new_files_are_found_by_name(tree):
    from bench import run as R
    cell = R.load_cell(CELL, tree)
    assert cell.cfg["stem"] == 8 and cell.traffic["schedule_seed"] == 5
    assert [m["name"] for m in cell.per_layer] == ["scenes_done.tinymix"]
    assert [m["name"] for m in cell.end_to_end] == ["latency_p50_ms",
                                                    "setup_s"]
    assert R.load_reader("scenes_done.tinymix", tree).__doc__ is None


def test_tiny_run_is_correct_and_reports_the_new_metric(tree):
    from bench import run as R
    out = R.run(CELL, 2**31 + 11, 1.0, True, root=tree, on_chip=False)
    assert out["correct"] is True, out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["scenes_done.tinymix"]["value"] > 0
    assert list(out)[-1] == "compared"
    assert out["compared"]["max_gap_rel"]["value"] <= \
        out["compared"]["max_gap_rel"]["limit"]


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        tree, monkeypatch):
    """Every served class id is shifted by one class inside the batched
    apply's output."""
    from bench import run as R
    from repro.serve import engine as E

    real_init = E.PointCloudEngine.__init__

    def broken_init(self, *a, **kw):
        real_init(self, *a, **kw)
        apply, n_classes = self._apply_batch, TINY_CFG["n_classes"]

        def altered(*args):
            out = apply(*args)
            return (out + 1) % n_classes

        self._apply_batch = altered

    monkeypatch.setattr(E.PointCloudEngine, "__init__", broken_init)
    out = R.run(CELL, 2**31 + 12, 1.0, False, root=tree, on_chip=False)
    assert out["correct"] is False
    assert out["compared"]["max_gap_rel"]["value"] > \
        out["compared"]["max_gap_rel"]["limit"]


def test_the_bf16_control_in_the_programs_place_is_not_correct(tree):
    """The reference computed in bfloat16, the next precision below the
    configuration's float32, stands in for the served class ids of every
    request; `compare` must find it over the served cell's limit.  The
    scenes hold some thousands of voxels: a widest gap is taken over
    many rows in the served cell too."""
    from bench import loadgen as LG
    from bench import run as R
    from bench.weights import make_params

    cell = R.load_cell(CELL, tree)
    cell.traffic["scene_voxels"] = [4000, 8000]
    seed = 2**31 + 13
    stream, _ = R.make_traffic(cell, seed)
    params = make_params(cell.cfg, int(cell.cfg["weights_seed"]))
    win = LG.Window(0.0, 1.0)
    for r in range(4):
        q = LG.Request(r, stream.base_of(r), r, 0.5, 0.5, 0.6, True)
        q.preds = R.reference_logits(cell.cfg, stream, params, q,
                                     dtype="bfloat16").argmax(-1)
        win.requests[r] = q
    got = R.compare(cell.cfg, stream, params, win, seed, 4)["max_gap_rel"]
    assert got["value"] > got["limit"]
    for q in win.requests.values():         # the reference itself passes
        q.preds = R.reference_logits(cell.cfg, stream, params, q).argmax(-1)
    got = R.compare(cell.cfg, stream, params, win, seed, 4)["max_gap_rel"]
    assert got["value"] == 0.0


def test_every_sample_holds_the_largest_and_the_smallest_scene():
    import numpy as np

    from bench import loadgen as LG
    from bench import run as R

    win = LG.Window(0.0, 1.0)
    for r, rows in enumerate([300, 120, 500, 80, 260, 410]):
        q = LG.Request(r, r, r, 0.5, 0.5, 0.6, True)
        q.preds = np.zeros(rows, np.int32)
        win.requests[r] = q
    for seed in (1, 2, 2**31 + 3):
        pick = R.pick_sample(win, seed, 3)
        assert [len(q.preds) for q in pick[:2]] == [500, 80]
        assert len({q.r for q in pick}) == 3
    assert [len(q.preds) for q in R.pick_sample(win, 5, 1)] == [500]
