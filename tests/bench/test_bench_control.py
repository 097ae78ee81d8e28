"""The comparison that decides ``correct`` fails where it must: a lower
precision (the program's own bf16 distance path; the CPU runs the chip's
control, ``Precision.HIGH``, in full float32) and each planted fault read as
not correct, while the sound program reads as correct, all at a tiny size on
the CPU through ``bench/control.py``."""
from __future__ import annotations

import json

import pytest

import benchkit

FAULTS = ["bf16_path", "state_unchanged", "half_batch", "answer_altered"]


@pytest.mark.parametrize("base", ["cord19-d768-k25", "hepmass-d28-k25"])
def test_control_and_faults_read_not_correct(tmp_path, base):
    root = benchkit.copy_bench(tmp_path)
    cell = benchkit.add_tiny_cell(root, base)
    proc = benchkit.run_entry(root, "control", [
        "--workload", cell, "--seconds", "1.5", "--seeds", f"4,{2 ** 40}",
        "--variants", ",".join(["sound", *FAULTS]), "--fault-seeds", "1"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    recs = [json.loads(x) for x in proc.stdout.strip().splitlines()]
    runs = [r for r in recs if "variant" in r]
    sound = [r for r in runs if r["variant"] == "sound"]
    assert len(sound) == 2 and all(r["correct"] for r in sound), sound
    for name in FAULTS:
        got = [r for r in runs if r["variant"] == name]
        assert len(got) == 1 and got[0]["correct"] is False, got
    assert "summary" in recs[-1]
