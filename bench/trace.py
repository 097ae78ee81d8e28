"""Reduction of a JAX profiler trace to what the per-layer metrics read:
device ops with their name scopes, module executions, device busy time,
collectives, and idle gaps named by what the host was doing.

It reads the ``*.trace.json.gz`` that ``jax.profiler`` writes beside the
``.xplane.pb``: plain gzip and JSON, one event per op, where each device op
carries its HLO text (``long_name``), category and name-scope path
(``tf_op``). Times here are integer nanoseconds on the trace's clock. The
traced slice is the span of the ``bench.slice`` annotation that the harness
opens right after the profiler starts and closes right before it stops.
"""
from __future__ import annotations

import glob
import gzip
import json
import re
from collections import defaultdict
from typing import NamedTuple

SLICE = "bench.slice"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)
_KERNEL = 'custom_call_target="tpu_custom_call"'
_FIRST_SHAPE = re.compile(r"=\s*\(?\s*[a-z0-9]+\[([0-9,]*)\]")
# Host events that enclose whole phases say nothing about one gap.
_HOST_SKIP = re.compile(r"^(bench\.slice$|\$threading\.py|\$time sleep$)")


class Op(NamedTuple):
    device: int
    name: str      # the HLO instruction
    scope: str     # the name-scope path (tf_op)
    category: str  # hlo_category
    text: str      # the HLO text (long_name)
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


class Module(NamedTuple):
    device: int
    name: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


class HostEvent(NamedTuple):
    thread: str
    name: str
    start: int
    dur: int


class Trace(NamedTuple):
    ops: list      # leaf device ops: control-flow ops that hold others left out
    modules: list
    host: list
    start: int     # the traced slice
    end: int
    devices: list

    @property
    def window_ns(self) -> int:
        return self.end - self.start


def _ns(us: float) -> int:
    return int(round(float(us) * 1000.0))


def _leaves(ops: list) -> list:
    """Drop the ops that enclose others on their line (``while``,
    ``conditional``): their children carry the time."""
    ops = sorted(ops, key=lambda o: (o.start, -o.dur))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start >= o.end]


def load(path: str) -> Trace:
    """Read a ``*.trace.json.gz``, or the newest one under a directory."""
    if not path.endswith(".json.gz"):
        found = sorted(glob.glob(f"{path}/**/*.trace.json.gz",
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .trace.json.gz under {path}")
        path = found[-1]
    with gzip.open(path, "rt") as f:
        events = json.load(f)["traceEvents"]
    planes, lines = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            planes[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            lines[(e["pid"], e["tid"])] = e["args"]["name"]
    ops_by_dev, modules, host = defaultdict(list), [], []
    window = None
    for e in events:
        if e.get("ph") != "X":
            continue
        plane = planes.get(e["pid"], "")
        line = lines.get((e["pid"], e["tid"]), "")
        start, dur = _ns(e["ts"]), _ns(e.get("dur", 0.0))
        m = _DEVICE_PLANE.match(plane)
        if m:
            dev = int(m.group(1))
            args = e.get("args", {})
            if line == "XLA Ops":
                ops_by_dev[dev].append(Op(
                    dev, e["name"], args.get("tf_op", ""),
                    args.get("hlo_category", ""), args.get("long_name", ""),
                    start, dur))
            elif line == "XLA Modules":
                modules.append(Module(dev, e["name"], start, dur))
        elif plane.startswith("/host:CPU"):
            if e["name"] == SLICE:
                window = (start, start + dur)
            host.append(HostEvent(f"{line}#{e['tid']}", e["name"], start,
                                  dur))
    ops = [o for dev in sorted(ops_by_dev) for o in _leaves(ops_by_dev[dev])]
    if window is None:
        stamps = [(o.start, o.end) for o in ops] + [
            (h.start, h.start + h.dur) for h in host]
        window = (min(s for s, _ in stamps), max(e for _, e in stamps)) \
            if stamps else (0, 0)
    return Trace(ops, modules, host, window[0], window[1],
                 sorted(ops_by_dev))


def _union(intervals, lo: int, hi: int) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(tr: Trace, device: int) -> int:
    """Time in the slice during which some op ran on ``device``."""
    iv = [(o.start, o.end) for o in tr.ops if o.device == device]
    return sum(e - s for s, e in _union(iv, tr.start, tr.end))


def mean_busy_s(tr: Trace) -> float:
    """Busy seconds in the slice, averaged over the devices traced."""
    if not tr.devices:
        return 0.0
    return sum(busy_ns(tr, d) for d in tr.devices) / len(tr.devices) / 1e9


def main_module(tr: Trace) -> str | None:
    """The module that took the most device time in the slice: the round
    program, whatever it is named."""
    total = defaultdict(int)
    for m in tr.modules:
        total[m.name] += m.dur
    return max(total, key=total.get) if total else None


def complete_runs(tr: Trace, module: str, device: int | None = None) -> list:
    """Executions of ``module`` on ``device`` (the first traced one by
    default) that began and ended inside the slice."""
    dev = tr.devices[0] if device is None and tr.devices else device
    return [m for m in tr.modules if m.name == module and m.device == dev
            and m.start >= tr.start and m.end <= tr.end]


def ops_in(tr: Trace, runs: list) -> list:
    """Ops that ran inside the given module executions, on their device."""
    spans = sorted((r.device, r.start, r.end) for r in runs)
    out = []
    for o in tr.ops:
        for dev, s, e in spans:
            if dev == o.device and s <= o.start and o.end <= e:
                out.append(o)
                break
    return out


def in_scope(op: Op, scope: str) -> bool:
    return scope in op.scope.split("/")


def is_collective(op: Op) -> bool:
    return bool(_COLLECTIVE.search(op.category)
                or _COLLECTIVE.search(op.name))


def is_kernel(op: Op) -> bool:
    """A Pallas kernel launch (a TPU custom call)."""
    return _KERNEL in op.text


def batch(op: Op) -> int:
    """The batch a launch covers: the product of its first result's dims
    but the last two (``vmap`` adds leading dims to a kernel's grid)."""
    m = _FIRST_SHAPE.search(op.text)
    dims = [int(x) for x in m.group(1).split(",") if x] if m else []
    n = 1
    for x in dims[:-2]:
        n *= x
    return n


def idle_gaps(tr: Trace, device: int | None = None, top: int = 10) -> list:
    """The longest gaps between device ops in the slice, each as
    ``[what the host was doing, seconds]``. The host side is, on each of
    the two threads that cover most of the gap, the shortest event that
    covers at least a third of it: typically the consumer waiting and the
    producer's work (``sanitize_window``, ``device_put``)."""
    dev = tr.devices[0] if device is None and tr.devices else device
    busy = _union([(o.start, o.end) for o in tr.ops if o.device == dev],
                  tr.start, tr.end)
    gaps, t = [], tr.start
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if tr.end > t:
        gaps.append((t, tr.end))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        length = e - s
        per_thread = {}
        for h in tr.host:
            if _HOST_SKIP.match(h.name):
                continue
            cover = min(e, h.start + h.dur) - max(s, h.start)
            if cover * 3 < length:
                continue
            best = per_thread.get(h.thread)
            if best is None or h.dur < best[1].dur:
                per_thread[h.thread] = (cover, h)
        ranked = sorted(per_thread.values(), key=lambda ch: -ch[0])[:2]
        label = " + ".join(h.name for _, h in ranked) or "host: unattributed"
        out.append([label, length / 1e9])
    return out


def top_ops(tr: Trace, top: int = 10) -> list:
    """``[[scope: op, seconds], ...]``: leaf device time in the slice summed
    by the op's innermost named scopes and HLO name, longest first."""
    total = defaultdict(int)
    for o in tr.ops:
        if tr.start <= o.start and o.end <= tr.end:
            named = [p for p in o.scope.split("/")
                     if p.startswith(("round.", "kernel."))]
            base = re.sub(r"\.\d+$", "", o.name)
            total[f"{'/'.join(named[-2:])}: {base}" if named else base] += \
                o.dur
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def kernel_share(ctx, scope: str, work) -> tuple[float, str] | None:
    """(percent of roofline, bounding resource) of the kernel under
    ``scope`` in the round program's complete executions in the slice, or
    None where the slice holds no launch. ``work(s, k, d)`` gives one
    call's (operations, bytes); a launch does it for each of its batch."""
    from bench import peaks

    tr = ctx.trace
    if tr is None:
        return None
    module = main_module(tr)
    runs = complete_runs(tr, module) if module else []
    ops = [o for o in ops_in(tr, runs) if in_scope(o, scope)]
    calls = sum(batch(o) for o in ops if is_kernel(o))
    if not calls:
        return None
    cfg = ctx.config
    flops, nbytes = work(cfg["sample_size"], cfg["k"], cfg["d"])
    t_min, bound = peaks.roofline_s(flops * calls, nbytes * calls, ctx.peak)
    spent = sum(o.dur for o in ops) / 1e9
    return 100.0 * t_min / spent, bound
