"""The reader of the reseed counter, ``reseed_rounds_pct``: the share of the
timed call's ``hpclust.round`` events in which some worker redrew a
degenerate row. On hand-built events; None where the trace holds no device,
or where the events carry no ``reseed_rows``, as a program that counts none
writes them."""
from __future__ import annotations

import gzip
import json
import sys

import pytest

from benchkit import ROOT

sys.path.insert(0, str(ROOT))

from bench import peaks, spec, trace  # noqa: E402

NAME = "reseed_rounds_pct"


def _trace(path, *, device=True):
    """A slice of 0..1000 us with one round-program execution in it."""
    ev = [{"ph": "M", "pid": 9, "name": "process_name",
           "args": {"name": "/host:CPU"}},
          {"ph": "X", "pid": 9, "tid": 1, "name": "bench.slice", "ts": 0.0,
           "dur": 1000.0, "args": {}}]
    if device:
        ev += [{"ph": "M", "pid": 1, "name": "process_name",
                "args": {"name": "/device:TPU:0"}},
               {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
                "args": {"name": "XLA Ops"}},
               {"ph": "X", "pid": 1, "tid": 2, "name": "fusion.1",
                "ts": 100.0, "dur": 50.0,
                "args": {"tf_op": "jit(run)/while/body/round.reseed/cond"}}]
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": ev}, f)
    return trace.load(str(path))


def _rounds(*rows):
    return [{"type": "event", "name": "hpclust.round",
             "attrs": {"round": r, "lloyd_iters": [3, 3],
                       "reseed_rows": list(w)}}
            for r, w in enumerate(rows)]


def _read(tr, records):
    ctx = spec.Context(tr, list(records), {"workers": 2},
                       {"rounds_per_window": 8},
                       peaks.Peak(flops_per_s=1e12, hbm_bytes_per_s=1e10))
    return spec.metric_reader(NAME)(ctx)


def test_declared_for_every_cell_as_a_program_counter():
    declared = {m["name"]: m for m in spec.load_spec()["per_layer"]}
    cells = [w["name"] for w in spec.load_spec()["workloads"]]
    m = declared[NAME]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "lower", "program_counter", "round body", "rows_per_s")
    assert m["workloads"] == cells


@pytest.mark.parametrize("rows, want", [
    ([[0, 0]] * 8, 0.0),
    ([[25, 25], [0, 2]] + [[0, 0]] * 6, 25.0),
    ([[1, 0]] * 8, 100.0),
])
def test_share_of_rounds_that_redrew(tmp_path, rows, want):
    tr = _trace(tmp_path / "t.trace.json.gz")
    other = [{"type": "span", "name": "stream.wait", "attrs": {}}]
    assert _read(tr, other + _rounds(*rows)) == pytest.approx(want)


def test_nothing_to_read_reads_none(tmp_path):
    tr = _trace(tmp_path / "t.trace.json.gz")
    # Round events of a program that counts no redrawn rows.
    old = [{"type": "event", "name": "hpclust.round",
            "attrs": {"round": 0, "lloyd_iters": [3, 3]}}]
    assert _read(tr, old) is None
    assert _read(tr, []) is None
    # No device traced (a CPU rehearsal), or no trace at all.
    hostonly = _trace(tmp_path / "host.trace.json.gz", device=False)
    assert _read(hostonly, _rounds([0, 1])) is None
    assert _read(None, _rounds([0, 1])) is None
