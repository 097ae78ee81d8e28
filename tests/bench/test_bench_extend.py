"""The harness is driven by data: a configuration, a traffic mix and a
per-layer metric added as new files are picked up, with no existing file of
the benchmark edited. And the entry refuses to measure where it cannot."""
from __future__ import annotations

import hashlib
import json

import benchkit

NEW_METRIC = '''"""windows_seen: windows the timed call finished, from its spans."""


def read(ctx):
    n = sum(1 for r in ctx.spans if r.get("name") == "stream.window")
    return float(n) or None
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_config_mix_and_metric_are_picked_up_from_new_files(tmp_path):
    root = benchkit.copy_bench(tmp_path)
    before = _digests(root)
    (root / "bench" / "traffic" / "twice.json").write_text(
        json.dumps({"rounds_per_window": 2, "why": "two rounds a window"}))
    (root / "bench" / "metrics" / "windows_seen.py").write_text(NEW_METRIC)
    cell = benchkit.add_tiny_cell(root, "cord19-d768-k25", name="newcfg",
                                  traffic="twice")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append({
        "name": "windows_seen", "unit": "windows", "better": "higher",
        "source": "program_span", "layer": "streaming entry",
        "moves": "rows_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _digests(root)
    assert {p: after[p] for p in before} == before  # nothing edited
    proc = benchkit.run_entry(root, "run", [
        "--workload", cell, "--seed", "9", "--seconds", "2", "--trace", "1"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = benchkit.last_json(proc.stdout)
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["windows_seen"]["value"] >= 1
    # two rounds per window from the new mix: the history holds them
    assert line["window"]["windows"] >= 1


def test_exits_nonzero_off_a_tpu(tmp_path):
    root = benchkit.copy_bench(tmp_path)
    proc = benchkit.run_entry(root, "run", [
        "--workload", "cord19-search", "--seed", "1", "--seconds", "1"],
        steer=False)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU chip" in proc.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    root = benchkit.copy_bench(tmp_path, with_src=False)
    proc = benchkit.run_entry(root, "run", [
        "--workload", "cord19-search", "--seed", "1", "--seconds", "1"],
        steer=False)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
