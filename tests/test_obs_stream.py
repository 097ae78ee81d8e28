"""The streaming path's spans, scopes and counters: ``stream.wait``,
``h2d.put``, ``sanitize.window`` (with its screen's ``threads`` and
``blocks``) and ``stream.sync`` once per window,
program spans mirrored into a ``jax.profiler`` trace, the ``round.reseed`` /
``round.lloyd`` scopes in the round program, and the Lloyd-iteration and
reseed counters against the program's own ``RoundMetrics``."""
from __future__ import annotations

import contextlib
import os
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import HPClust, HPClustConfig, hpclust, strategies
from repro.data.device_prefetch import device_stream
from repro.obs import jaxhooks
from repro.resilience.sanitize import screen_plan

ROOT = Path(__file__).resolve().parents[1]

CFG = HPClustConfig(k=3, sample_size=64, workers=2, rounds=2)
WINDOWS = 3


def _windows(n=WINDOWS, rows=256, d=4):
    rng = np.random.default_rng(0)
    return [rng.normal(size=(rows, d)).astype(np.float32) for _ in range(n)]


@pytest.fixture
def configured():
    """A recorder installed by ``obs.configure()``, as the launch CLIs and
    the benchmark install one; the previous recorder is restored."""
    sink = obs.ListSink()
    prev = obs.get_recorder()
    rec = obs.configure(sinks=(sink,))
    yield rec, sink
    obs.set_recorder(prev)


def _spans(sink, name):
    return [r for r in sink.records
            if r["type"] == "span" and r["name"] == name]


@pytest.mark.parametrize("depth", [0, 2])
def test_stream_spans_once_per_window(configured, depth):
    _, sink = configured
    res = HPClust(CFG, prefetch=depth).fit_stream(_windows())
    assert res.stats.windows == WINDOWS
    for name in ("sanitize.window", "h2d.put", "stream.sync",
                 "stream.window", "hpclust.rounds"):
        assert len(_spans(sink, name)) == WINDOWS, name
    # Tiny windows are screened inline, in one row block.
    assert all(s["attrs"] == {"threads": 1, "blocks": 1}
               for s in _spans(sink, "sanitize.window"))
    waits = _spans(sink, "stream.wait")
    assert [w["attrs"]["window"] for w in waits
            if "window" in w["attrs"]] == list(range(WINDOWS))
    # With a prefetch thread the consumer also waits for the stream's end.
    assert len(waits) == WINDOWS + (depth > 0)
    # sanitize and H2D run where _prepare runs: the prefetch thread, or the
    # consumer's own wait on the synchronous path.
    main = waits[0]["thread"]
    for name in ("sanitize.window", "h2d.put"):
        assert all((s["thread"] == main) == (depth == 0)
                   for s in _spans(sink, name)), name
    if depth == 0:
        by_id = {r["span_id"]: r for r in sink.records if r["type"] == "span"}
        assert all(by_id[s["parent_id"]]["name"] == "stream.wait"
                   for s in _spans(sink, "h2d.put"))


@pytest.mark.parametrize("depth", [0, 2])
def test_sanitize_span_reports_the_screen_plan(configured, depth):
    _, sink = configured
    tiny = _windows(1)[0]
    rows = screen_plan((1, 768)).rows
    big = _windows(1, rows=3 * rows + 5, d=768)[0]
    items = list(device_stream([tiny, big], depth=depth, place=lambda w: w))
    assert [it.n_bad for it in items] == [0, 0]
    tiny_span, big_span = _spans(sink, "sanitize.window")
    assert tiny_span["attrs"] == {"threads": 1, "blocks": 1}
    assert big_span["attrs"]["blocks"] == 4
    if len(os.sched_getaffinity(0)) > 1:
        assert big_span["attrs"]["threads"] > 1


def test_stream_sync_follows_dispatch_inside_the_window(configured):
    _, sink = configured
    HPClust(CFG, prefetch=0).fit_stream(_windows(2))
    by_id = {r["span_id"]: r for r in sink.records if r["type"] == "span"}
    for name in ("hpclust.rounds", "stream.sync"):
        assert all(by_id[s["parent_id"]]["name"] == "stream.window"
                   for s in _spans(sink, name))
    for rounds, sync in zip(_spans(sink, "hpclust.rounds"),
                            _spans(sink, "stream.sync")):
        assert rounds["ts"] + rounds["dur"] <= sync["ts"]


def test_program_spans_land_in_the_profiler_trace(configured, tmp_path):
    sys.path.insert(0, str(ROOT))
    from bench import trace

    HPClust(CFG, prefetch=2).fit_stream(_windows(1))  # compile outside
    jax.profiler.start_trace(str(tmp_path))
    try:
        HPClust(CFG, prefetch=2).fit_stream(_windows(2))
    finally:
        jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    names = {h.name for h in tr.host}
    assert {"stream.wait", "sanitize.window", "h2d.put", "stream.sync",
            "stream.window", "hpclust.rounds"} <= names
    # The producer's spans are on a thread of their own.
    threads = {n: {h.thread for h in tr.host if h.name == n}
               for n in ("stream.wait", "h2d.put")}
    assert threads["stream.wait"].isdisjoint(threads["h2d.put"])


def test_no_recorder_builds_no_annotation(monkeypatch):
    built = []

    def counting(name):
        built.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jaxhooks, "trace_annotation", counting)
    prev = obs.set_recorder(None)
    try:
        assert obs.span("stream.wait") is obs.NULL_SPAN
        HPClust(CFG, prefetch=2).fit_stream(_windows(2))
        assert built == []
        # A bare Recorder mirrors nothing either.
        obs.set_recorder(obs.Recorder((obs.ListSink(),)))
        with obs.span("stream.wait"):
            pass
        assert built == []
        # configure() mirrors every span through the hook, by name.
        obs.configure(sinks=(obs.ListSink(),))
        with obs.span("stream.wait"):
            with obs.span("h2d.put"):
                pass
        assert built == ["stream.wait", "h2d.put"]
    finally:
        obs.set_recorder(prev)


def test_round_program_carries_reseed_and_lloyd_scopes():
    x = _windows(1)[0]
    state = strategies.init_state(jax.random.PRNGKey(0), CFG, x.shape[1])
    lowered = hpclust._jit_run_from_state.lower(state, x, cfg=CFG)
    text = lowered.as_text(debug_info=True)
    assert "round.reseed" in text and "round.lloyd" in text
    # In the compiled program's op names the reseed's conditional sits in
    # the round body outside the vmap over workers, round.lloyd under it,
    # and the kernel scopes stay parts of the path beneath round.lloyd.
    names = set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))
    worker = "round.worker_round/vmap("
    assert any(n.endswith("/while/body/closed_call/round.reseed/cond")
               for n in names)
    assert not any("vmap(round.reseed)" in n for n in names)
    for kernel in ("kernel.assign", "kernel.update"):
        assert any(f"{worker}round.lloyd)/" in n and f"/{kernel}/" in n
                   for n in names), kernel
    assert not any("round.reseed" in n and "kernel." in n for n in names)


@pytest.mark.parametrize("entry", ["fit", "fit_stream"])
def test_lloyd_iters_match_round_metrics(configured, entry):
    rec, sink = configured
    xs = _windows(2)
    est = HPClust(CFG, seed=3, prefetch=0)
    key = jax.random.PRNGKey(3)
    if entry == "fit":
        est.fit(xs[0])
        _, m = hpclust._jit_run_hpclust(key, xs[0], cfg=CFG)
        want = [np.asarray(m.kmeans_iters)]
    else:
        est.fit_stream(xs)
        key, k0 = jax.random.split(key)
        state = strategies.init_state(k0, CFG, xs[0].shape[1])
        want = []
        for x in xs:  # fit_stream's windows, replayed without donation
            state, m = hpclust._jit_run_from_state(state, x, cfg=CFG)
            want.append(np.asarray(m.kmeans_iters))
    want = np.concatenate(want)             # (rounds, workers)
    events = [r["attrs"] for r in sink.records
              if r["type"] == "event" and r["name"] == "hpclust.round"]
    got = np.array([e["lloyd_iters"] for e in events])
    np.testing.assert_array_equal(got, want)
    assert rec.metrics.counter("hpclust.lloyd_iters").snapshot() == \
        want.sum()


@pytest.mark.parametrize("depth", [0, 2])
def test_reseed_rows_match_round_metrics(configured, depth):
    rec, sink = configured
    xs = _windows(2)
    HPClust(CFG, seed=3, prefetch=depth).fit_stream(xs)
    key, k0 = jax.random.split(jax.random.PRNGKey(3))
    state = strategies.init_state(k0, CFG, xs[0].shape[1])
    want = []
    for x in xs:  # fit_stream's windows, replayed without donation
        state, m = hpclust._jit_run_from_state(state, x, cfg=CFG)
        want.append(np.asarray(m.reseed_rows))
    want = np.concatenate(want)             # (rounds, workers)
    assert want[0].tolist() == [CFG.k] * CFG.workers  # the fresh start
    events = [r["attrs"] for r in sink.records
              if r["type"] == "event" and r["name"] == "hpclust.round"]
    got = np.array([e["reseed_rows"] for e in events])
    np.testing.assert_array_equal(got, want)
    counter = rec.metrics.counter
    assert counter("hpclust.reseed_rows").snapshot() == want.sum()
    assert counter("hpclust.reseed_rounds").snapshot() == \
        (want.sum(axis=1) > 0).sum()


def test_traced_fit_stream_matches_untraced():
    xs = _windows()
    base = HPClust(CFG, prefetch=2).fit_stream(xs)
    sink = obs.ListSink()
    prev = obs.get_recorder()
    obs.configure(sinks=(sink,))
    try:
        traced = HPClust(CFG, prefetch=2).fit_stream(xs)
    finally:
        obs.set_recorder(prev)
    np.testing.assert_array_equal(traced.centroids, base.centroids)
    np.testing.assert_array_equal(traced.history, base.history)
    assert traced.objective == base.objective
