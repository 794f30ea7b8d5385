"""Fault tolerance drill: kill training at step N, restart, and verify the
resumed run reaches the same final state as an uninterrupted run."""
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _train(tmp, steps, fail_at=None, seed=0):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"       # the child never races for a chip
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if fail_at is not None:
        env["REPRO_FAIL_AT_STEP"] = str(fail_at)
    else:
        env.pop("REPRO_FAIL_AT_STEP", None)
    cmd = [sys.executable, "-m", "repro.launch.train", "--arch",
           "stablelm-1.6b", "--reduced", "--steps", str(steps), "--batch",
           "2", "--seq-len", "16", "--ckpt-dir", tmp, "--ckpt-every", "4",
           "--log-every", "4", "--seed", str(seed)]
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=900)


def test_injected_failure_then_resume(tmp_path):
    d1 = str(tmp_path / "interrupted")
    # run 1: dies at step 10 (after the step-8 checkpoint committed)
    r = _train(d1, steps=16, fail_at=10)
    assert r.returncode != 0
    assert "injected failure" in (r.stdout + r.stderr)
    # restart: resumes from the last committed checkpoint and finishes
    r2 = _train(d1, steps=16)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "resumed from step" in r2.stdout

    # uninterrupted reference run
    d2 = str(tmp_path / "clean")
    r3 = _train(d2, steps=16)
    assert r3.returncode == 0

    # final checkpoints agree bit-exactly (deterministic data + resume)
    a = np.load(os.path.join(d1, "step_16", "leaf_0.npy"))
    b = np.load(os.path.join(d2, "step_16", "leaf_0.npy"))
    np.testing.assert_array_equal(a, b)


def test_loss_improves_over_training(tmp_path):
    r = _train(str(tmp_path / "ck"), steps=30)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "improved" in r.stdout and "NOT improved" not in r.stdout, r.stdout
