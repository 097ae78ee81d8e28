"""CPU rehearsals of ``bench/run.py``: every configuration, cut to a tiny
size, prints the contract's last line; a traced run prints its per-layer
metrics and breakdown. The TPU check is steered from the test."""
from __future__ import annotations

import pytest

import benchkit

CONFIGS = {
    "cord19-d768-k25": {},
    "hepmass-d28-k25": {},
}


@pytest.mark.parametrize("base", sorted(CONFIGS))
def test_each_configuration_prints_the_contract_line(tmp_path, base):
    root = benchkit.copy_bench(tmp_path)
    cell = benchkit.add_tiny_cell(root, base)
    seed = 2 ** 33 + 101
    proc = benchkit.run_entry(root, "run", [
        "--workload", cell, "--seed", str(seed), "--seconds", "2",
        "--trace", "0"], env=CONFIGS[base])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = benchkit.last_json(proc.stdout)
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert line["window"]["compiles"] == 0
    assert line["window"]["holdout_obj_ratio"] > 0
    # Each compared number and its limit close standard error too.
    tail = proc.stderr.strip().splitlines()[-len(line["checks"]):]
    assert [t.split("=")[0] for t in tail] == [
        f"check {n}" for n in line["checks"]]


def test_traced_run_prints_per_layer_metrics_and_breakdown(tmp_path):
    root = benchkit.copy_bench(tmp_path)
    cell = benchkit.add_tiny_cell(root, "cord19-d768-k25")
    proc = benchkit.run_entry(root, "run", [
        "--workload", cell, "--seed", "5", "--seconds", "3", "--trace", "1"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = benchkit.last_json(proc.stdout)
    assert line["correct"] is True, line["checks"]
    # The CPU has no TPU planes: only the program-span metric finds
    # something to read, and no device metric is made up.
    assert set(line["metrics"]) == {"sanitize_ms"}
    assert line["metrics"]["sanitize_ms"]["value"] > 0
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(line)[-1] == "checks"
