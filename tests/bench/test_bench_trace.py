"""The trace reduction (``bench/trace.py``) and the per-layer readers: on a
hand-built trace whose every number is known, and on a small trace recorded
on a TPU v5e chip by a ``--trace 1`` run of the cord19-ingest cell."""
from __future__ import annotations

import gzip
import json
import sys

import pytest

from benchkit import ROOT

sys.path.insert(0, str(ROOT))

from bench import peaks, spec, trace  # noqa: E402

FIXTURE = ROOT / "tests" / "bench" / "fixtures" / "cord19_ingest.trace.json.gz"
KERNEL = 'custom_call_target="tpu_custom_call"'


def _x(pid, tid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": args}


def _hand_trace(path):
    """Times in microseconds. One device; the slice runs 0..1000; the round
    program runs twice (100..400, 600..900, one round each) and once more
    across the slice's end."""
    scope = "jit(run)/while/body/round.worker_round"
    assign = f"{scope}/kernel.assign/pallas_call"
    update = f"{scope}/kernel.update/pallas_call"
    ev = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 9, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 9, "tid": 1, "name": "thread_name",
         "args": {"name": "python3"}},
        {"ph": "M", "pid": 9, "tid": 2, "name": "thread_name",
         "args": {"name": "python3"}},
        _x(9, 1, "bench.slice", 0.0, 1000.0),
        _x(9, 2, "$sanitize.py:20 sanitize_window", 390.0, 220.0),
        _x(9, 1, "$device_prefetch.py:85 device_stream", 380.0, 240.0),
    ]
    for t0 in (100.0, 600.0, 950.0):
        ev += [
            _x(1, 1, "jit_run(1)", t0, 300.0),
            _x(1, 2, "while.1", t0, 300.0, hlo_category="while", tf_op=""),
            _x(1, 2, "fusion.1", t0, 100.0, hlo_category="loop fusion",
               tf_op=f"{assign.rsplit('/', 1)[0]}/jit(_pad)/pad",
               long_name="%fusion.1 = f32[8,64,128] fusion(...)"),
            _x(1, 2, "assign_pallas.2", t0 + 100, 50.0,
               hlo_category="custom-call", tf_op=assign,
               long_name=f"%assign_pallas.2 = (s32[8,64,1], f32[8,64,1]) "
                         f"custom-call(...), {KERNEL}"),
            _x(1, 2, "cluster_sums_pallas.3", t0 + 150, 50.0,
               hlo_category="custom-call", tf_op=update,
               long_name=f"%cluster_sums_pallas.3 = (f32[8,128,128], "
                         f"f32[8,128,1]) custom-call(...), {KERNEL}"),
            _x(1, 2, "multiply_reduce_fusion.4", t0 + 200, 100.0,
               hlo_category="loop fusion", tf_op=scope,
               long_name="%multiply_reduce_fusion.4 = f32[8,64] fusion()"),
        ]
    ev.append(_x(1, 2, "all-reduce.9", 560.0, 20.0, hlo_category="all-reduce",
                 tf_op="jit(other)", long_name="%all-reduce.9 = f32[8]"))
    with gzip.open(path, "wt") as f:
        json.dump({"traceEvents": ev}, f)
    return path


@pytest.fixture
def hand(tmp_path):
    return trace.load(str(_hand_trace(tmp_path / "t.trace.json.gz")))


def _ctx(tr, **cfg):
    config = {"sample_size": 64, "k": 4, "d": 100, "workers": 8, **cfg}
    return spec.Context(tr, [], config, {"rounds_per_window": 1},
                        peaks.Peak(flops_per_s=1e12, hbm_bytes_per_s=1e10))


def test_hand_trace_reduces_to_known_numbers(hand):
    assert hand.devices == [0] and hand.window_ns == 1_000_000
    assert all(o.category != "while" for o in hand.ops)   # parents dropped
    # busy: 100..400, 560..580, 600..900, 950..1000 (clipped at the end)
    assert trace.busy_ns(hand, 0) == 300_000 + 20_000 + 300_000 + 50_000
    assert trace.main_module(hand) == "jit_run(1)"
    runs = trace.complete_runs(hand, "jit_run(1)")
    assert [r.start for r in runs] == [100_000, 600_000]
    launches = [o for o in trace.ops_in(hand, runs) if trace.is_kernel(o)]
    assert len(launches) == 4 and {trace.batch(o) for o in launches} == {8}
    assert [o.name for o in hand.ops if trace.is_collective(o)] == [
        "all-reduce.9"]
    gaps = trace.idle_gaps(hand)
    assert gaps[0] == [
        "$sanitize.py:20 sanitize_window + "
        "$device_prefetch.py:85 device_stream", 160e-6]
    assert gaps[1] == ["host: unattributed", 100e-6]
    assert trace.top_ops(hand)[0][0] in (
        "round.worker_round: multiply_reduce_fusion",
        "round.worker_round/kernel.assign: fusion")


def test_readers_on_the_hand_trace(hand):
    ctx = _ctx(hand)
    read = {m: spec.metric_reader(m)(ctx) for m in (
        "assign_roofline_pct", "update_roofline_pct", "round_body_ms",
        "device_idle_pct", "sanitize_ms")}
    # assign: 2 launches x 8 calls of (2*64*4*100 ops, 64*100*4 + 4*100*4
    # + 64*8 bytes) = 16 x 27,712 bytes; memory-bound at 1e10 B/s, over
    # 2 x (100 + 50) us spent under the scope, the pad included.
    assert read["assign_roofline_pct"] == pytest.approx(
        100 * 16 * 27_712 / 1e10 / 300e-6)
    # update: 16 x (64*100*4 + 64*4 + 4*100*4 + 4*4) bytes over 2 x 50 us.
    assert read["update_roofline_pct"] == pytest.approx(
        100 * 16 * 27_472 / 1e10 / 100e-6)
    # outside the kernel scopes: 100 us a run, one round a run
    assert read["round_body_ms"] == pytest.approx(0.1)
    assert read["device_idle_pct"] == pytest.approx(100 * (1 - 0.67))
    assert read["sanitize_ms"] is None       # no spans were recorded


def test_trace_recorded_on_the_chip():
    tr = trace.load(str(FIXTURE))
    assert tr.devices == [0]
    busy = trace.mean_busy_s(tr)
    assert 0 < busy * 1e9 <= tr.window_ns
    cell = spec.load_cell("cord19-ingest")
    ctx = spec.Context(tr, [], cell.config, cell.traffic,
                       peaks.peak("TPU v5 lite"))
    assign = spec.metric_reader("assign_roofline_pct")(ctx)
    update = spec.metric_reader("update_roofline_pct")(ctx)
    assert 0 < assign <= 100 and 0 < update <= 100
    assert spec.metric_reader("round_body_ms")(ctx) > 0
    idle = spec.metric_reader("device_idle_pct")(ctx)
    assert 0 <= idle < 100
    # A round program's leaf ops fit inside its execution.
    runs = trace.complete_runs(tr, trace.main_module(tr))
    assert runs
    for r in runs:
        inside = trace.ops_in(tr, [r])
        assert sum(o.dur for o in inside) <= r.dur
    # The host work that starves the device in this cell is named.
    assert any("sanitize_window" in label
               for label, _ in trace.idle_gaps(tr))
