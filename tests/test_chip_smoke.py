"""chip_smoke.py on the CPU: its phases at tiny width and size, and its
refusal of a host without a TPU."""

import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as CS  # noqa: E402

TINY = dict(stem=8, enc_planes=(8, 16), dec_planes=(16, 8),
            blocks_per_stage=1)


def test_chip_smoke_phases_tiny():
    """Serve -> per-scene gms reference -> agreement, then the Pallas
    flows (interpret mode here) against fod on one bucket-padded scene."""
    scenes = CS.make_scenes(3, sizes=(90, 40), seed=1)
    assert [c.shape for c, _, _ in scenes] == [(90, 4), (40, 4), (90, 4)]
    assert all(m.all() for _, m, _ in scenes)
    params = CS.init_params(0, **TINY)
    preds, engine, stats = CS.serve(params, scenes, max_batch=2)
    assert stats["n_completed"] == 3
    refs = CS.reference_logits(params, scenes)
    reports = CS.check_served(preds, refs, scenes)
    assert all(r["ok"] and r["agree"] > 0.9 for r in reports)

    logits, _, _, mask = CS.flow_logits(params, engine, scenes[0],
                                     ("fod",) + CS.PALLAS_FLOWS)
    assert mask.shape == (128,) and mask.sum() == scenes[0][1].sum()
    for flow in CS.PALLAS_FLOWS:
        assert CS.rel_err(logits[flow], logits["fod"], mask) <= 1e-4


def test_check_preds_rejects_a_wrong_class():
    """The agreement rule passes near-ties and fails a clear miss."""
    ref = np.array([[1.0, 0.999, -1.0], [0.0, 2.0, 1.0]])
    mask = np.array([True, True])
    assert CS.check_preds(np.array([1, 1]), ref, mask)["ok"]
    assert not CS.check_preds(np.array([0, 2]), ref, mask)["ok"]


def test_chip_smoke_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    for line in r.stdout.splitlines():
        try:
            assert json.loads(line).get("ok") is not True
        except (ValueError, AttributeError):
            pass
