"""CLI driver smoke tests (train / serve / cluster / examples)."""
import json
import subprocess
import sys
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


def _run(args, timeout=900):
    out = subprocess.run(
        [sys.executable] + args, capture_output=True, text=True,
        env=ENV, timeout=timeout, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_cluster_driver():
    out = _run(["-m", "repro.launch.cluster", "--windows", "2",
                "--window-size", "8192", "--rounds", "3", "--sample", "512",
                "--workers", "2"])
    rec = json.loads(out[out.index("{"):])
    assert rec["sample_objective"] > 0
    assert rec["rounds_total"] == 6
    # The record names the device it ran on: a run off the chip shows it.
    assert rec["device"]["platform"] == "cpu" and rec["device"]["count"] >= 1


def test_train_driver_loss_improves():
    import shutil
    # fresh checkpoint dir: the Trainer intentionally resumes from any
    # existing checkpoints (that's the fault-tolerance contract)
    shutil.rmtree(os.path.join(REPO, "checkpoints/_test_train"),
                  ignore_errors=True)
    out = _run(["-m", "repro.launch.train", "--steps", "40", "--batch", "4",
                "--seq", "32", "--ckpt-dir", "checkpoints/_test_train"])
    rec = json.loads(out[out.index("{"):])
    assert rec["status"] == "done"
    # statistical check: training makes progress and never blows up
    assert rec["loss_min"] < rec["loss_first"]
    assert rec["loss_last"] < rec["loss_first"] * 1.05


def test_serve_driver():
    out = _run(["-m", "repro.launch.serve", "--requests", "4", "--slots", "2",
                "--max-tokens", "4", "--prompt-len", "8"])
    rec = json.loads(out[out.index("{"):])
    assert rec["completed"] == 4


def test_cluster_driver_sharded_engine():
    out = _run(["-m", "repro.launch.cluster", "--sharded", "--k", "4",
                "--sample", "256", "--rounds", "4", "--windows", "1",
                "--window-size", "8192"])
    rec = json.loads(out[out.index("{"):])
    assert rec["engine"] == "shard_map"
    assert rec["monotone"] is True
    assert rec["device"]["platform"] == "cpu"
