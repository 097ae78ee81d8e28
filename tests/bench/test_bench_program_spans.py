"""The readers of the program's spans, scope and counter: ``ingest_wait_ms``,
``h2d_ms``, ``window_sync_ms`` (``bench/program_spans.py``), ``reseed_ms``
and ``lloyd_iters``. On a hand-built trace whose every number is known;
where the slice holds no such event, or the trace no device, each reads
None, as on the chip-recorded trace of a program that wrote none."""
from __future__ import annotations

import gzip
import json
import sys

import pytest

from benchkit import ROOT

sys.path.insert(0, str(ROOT))

from bench import peaks, spec, trace  # noqa: E402

FIXTURE = ROOT / "tests" / "bench" / "fixtures" / "cord19_ingest.trace.json.gz"
NEW = ("ingest_wait_ms", "h2d_ms", "window_sync_ms", "reseed_ms",
       "lloyd_iters")
SPAN_READERS = {"ingest_wait_ms": "stream.wait", "h2d_ms": "h2d.put",
                "window_sync_ms": "stream.sync"}


def _x(pid, tid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": args}


def _hand_trace(path, *, device=True, spans=True):
    """Times in microseconds. The slice runs 0..1000; the round program
    runs twice inside it (100..400, 600..900, two rounds each) and once
    across its end. Each run: 40 us of reseed (vmapped scope), 60 us more
    round body, 100 us under kernel.assign beneath round.lloyd."""
    body = "jit(run)/while/body/closed_call/round.worker_round"
    ev = [
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
         "args": {"name": "python3"}},
        {"ph": "M", "pid": 9, "tid": 2, "name": "thread_name",
         "args": {"name": "python3"}},
        _x(9, 1, "bench.slice", 0.0, 1000.0),
    ]
    if device:
        ev += [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Modules"}},
            {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
        ]
        for t0 in (100.0, 600.0, 950.0):
            ev += [
                _x(1, 1, "jit_run(1)", t0, 300.0),
                _x(1, 2, "multiply_reduce_fusion.1", t0, 30.0,
                   tf_op=f"{body}/vmap(round.reseed)/dot_general"),
                _x(1, 2, "fusion.2", t0 + 30, 10.0,
                   tf_op=f"{body}/vmap(round.reseed)/while/body/add"),
                _x(1, 2, "fusion.3", t0 + 40, 60.0, tf_op=f"{body}/mul"),
                _x(1, 2, "assign_pallas.4", t0 + 100, 100.0,
                   tf_op=f"{body}/vmap(round.lloyd)/jit(_assign_clusters_jit)"
                         f"/kernel.assign/jit(assign_pallas)"),
            ]
    if spans:
        ev += [
            # consumer: two waits and two syncs inside, one wait across the
            # slice's start and one sync across its end
            _x(9, 1, "stream.wait", -50.0, 100.0),
            _x(9, 1, "stream.wait", 150.0, 200.0),
            _x(9, 1, "stream.wait", 500.0, 100.0),
            _x(9, 1, "stream.sync", 400.0, 20.0),
            _x(9, 1, "stream.sync", 900.0, 40.0),
            _x(9, 1, "stream.sync", 980.0, 50.0),
            # producer
            _x(9, 2, "sanitize.window", 200.0, 300.0),
            _x(9, 2, "h2d.put", 500.0, 30.0),
            _x(9, 2, "h2d.put", 990.0, 30.0),
        ]
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": ev}, f)
    return trace.load(str(path))


def _round_events(*iters):
    return [{"type": "event", "name": "hpclust.round",
             "attrs": {"round": r, "lloyd_iters": list(it)}}
            for r, it in enumerate(iters)]


def _ctx(tr, records=(), rounds_per_window=2):
    config = {"sample_size": 64, "k": 4, "d": 100, "workers": 2}
    return spec.Context(tr, list(records), config,
                        {"rounds_per_window": rounds_per_window},
                        peaks.Peak(flops_per_s=1e12, hbm_bytes_per_s=1e10))


def _read(name, ctx):
    return spec.metric_reader(name)(ctx)


def test_the_new_metrics_are_declared_for_every_cell():
    declared = {m["name"]: m for m in spec.load_spec()["per_layer"]}
    cells = [w["name"] for w in spec.load_spec()["workloads"]]
    for name in NEW:
        assert declared[name]["moves"] == "rows_per_s"
        assert declared[name]["workloads"] == cells


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_span_readers_take_the_mean_inside_the_slice(tmp_path, name):
    tr = _hand_trace(tmp_path / "t.trace.json.gz")
    want = {"ingest_wait_ms": (0.2 + 0.1) / 2, "h2d_ms": 0.03,
            "window_sync_ms": (0.02 + 0.04) / 2}[name]
    assert _read(name, _ctx(tr)) == pytest.approx(want)


def test_reseed_ms_reads_the_vmapped_scope_per_round(tmp_path):
    tr = _hand_trace(tmp_path / "t.trace.json.gz")
    ctx = _ctx(tr)
    # 40 us a run, two complete runs of two rounds each
    assert _read("reseed_ms", ctx) == pytest.approx(0.04 * 2 / 4)
    # a part of the round body: 100 us a run outside the kernel scope
    assert _read("round_body_ms", ctx) == pytest.approx(0.1 * 2 / 4)
    assert _read("reseed_ms", ctx) <= _read("round_body_ms", ctx)


def test_lloyd_iters_is_the_mean_of_per_round_worker_means(tmp_path):
    tr = _hand_trace(tmp_path / "t.trace.json.gz")
    records = _round_events([4, 6], [10, 10], [1, 3]) + [
        {"type": "span", "name": "stream.wait", "attrs": {}}]
    assert _read("lloyd_iters", _ctx(tr, records)) == pytest.approx(
        (5 + 10 + 2) / 3)


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_reads_none(tmp_path, name):
    # The slice holds no such span, scope or event: a program that writes
    # none of them, as the parent of these metrics wrote none.
    bare = _hand_trace(tmp_path / "bare.trace.json.gz", spans=False)
    unscoped = bare._replace(ops=[o._replace(scope="jit(run)/mul")
                                  for o in bare.ops])
    old_events = [{"type": "event", "name": "hpclust.round",
                   "attrs": {"round": 0, "best_obj": 1.0}}]
    assert _read(name, _ctx(unscoped, old_events)) is None
    # A trace with no device (a CPU rehearsal) reads None too.
    hostonly = _hand_trace(tmp_path / "host.trace.json.gz", device=False)
    assert _read(name, _ctx(hostonly, _round_events([3, 5]))) is None
    assert _read(name, _ctx(None, _round_events([3, 5]))) is None


@pytest.mark.parametrize("name", NEW)
def test_chip_trace_of_a_program_without_them_reads_none(name):
    tr = trace.load(str(FIXTURE))
    cell = spec.load_cell("cord19-ingest")
    ctx = spec.Context(tr, [], cell.config, cell.traffic,
                       peaks.peak("TPU v5 lite"))
    assert _read(name, ctx) is None
