"""Tile-size autotuner for the Pallas kernels.

The wrappers in ``repro.kernels.ops`` pick ``(block_s, block_k, block_d)``
with fixed heuristics (``min(256, ...)``-style). Those defaults are sane on
one TPU generation at the paper's shapes, but the VMEM budget, MXU shape and
grid overheads all move with backend and problem size — on the "fast as the
hardware allows" north star the tile choice is a measurable multiplier on the
assign/update hot loop.

This module closes the loop:

  * ``candidates()`` enumerates hardware-aligned tile triples whose working
    set fits the static VMEM budget (the same budget the PK002 static
    analysis check enforces on kernel sites);
  * ``probe()`` times each candidate on a short synthetic run of the real
    kernel (compile excluded — one warmup call, then a timed median) and
    returns the winner;
  * winners persist in a JSON cache keyed by ``(backend, kernel,
    shape-bucket, dtype)`` so one probe serves every subsequent process.

``ops.py`` consults ``lookup()`` at trace time — a pure in-memory dict read
after the first file load — and falls back to its heuristics whenever the
feature is off (``REPRO_AUTOTUNE`` unset), the cache misses, or probing is
not allowed. Shape *buckets* (next power of two per dim) keep the cache
small and make one probe cover the whole jit-retrace neighbourhood.

Cache format (docs/performance.md §Autotuner)::

    {"version": 1,
     "entries": {"cpu/assign/s4096/k128/d256/f32":
                 {"blocks": [256, 128, 256], "us": 812.4}}}
"""
from __future__ import annotations

import json
import os
import statistics
import threading
import time
from typing import Callable, Iterable, Optional

from repro import flags

_LANE = 128
_SUBLANE = {"f32": 8, "bf16": 16}

# Conservative per-core VMEM budget for one kernel's working set. Real cores
# have ~16 MiB; Pallas double-buffers grid inputs, so target half of it.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

CACHE_VERSION = 1

_lock = threading.Lock()
_mem_cache: dict[str, dict] | None = None
_mem_cache_path: str | None = None


def _round_up(v: int, m: int) -> int:
    return v + (-v) % m


def _bucket(v: int) -> int:
    """Next power of two >= v (shape bucket — one probe per neighbourhood)."""
    b = 1
    while b < v:
        b *= 2
    return b


def _bytes(dtype: str) -> int:
    return 2 if dtype == "bf16" else 4


def vmem_bytes(kernel: str, bs: int, bk: int, bd: int, *,
               k_total: int | None = None, dtype: str = "f32") -> int:
    """Static VMEM working-set estimate for one grid step of ``kernel``.

    Mirrors the BlockSpecs/scratch in assign.py / update.py / lloyd.py; kept
    deliberately simple (inputs + outputs + scratch, no pipelining factor —
    the halved ``VMEM_BUDGET_BYTES`` accounts for double buffering).
    """
    eb = _bytes(dtype)
    if kernel == "assign":
        # xn (bs,1) + cn (1,bk) + x (bs,bd) + c (bk,bd) tiles, f32 acc
        # (bs,bk) scratch, (bs,1) best/bidx scratch, (bs,1) x2 outputs.
        return (
            bs * 4 + bk * 4 + bs * bd * eb + bk * bd * eb
            + bs * bk * 4 + bs * 4 + bs * 4 + bs * 8
        )
    if kernel == "update":
        # idx (bs,1) + x (bs,bd) in, sums (bk,bd) + counts (bk,1) resident.
        return bs * 4 + bs * bd * eb + bk * bd * 4 + bk * 4
    if kernel == "lloyd":
        # full-D row blocks: x (bs,D) + c (bk,D) + resident sums (K,D).
        kt = k_total if k_total is not None else bk
        return (
            bk * 4 + bs * bd * eb + bk * bd * eb + kt * bd * 4 + kt * 4
            + bs * 8 + bs * 8
        )
    raise ValueError(f"unknown kernel {kernel!r}")


def candidates(
    kernel: str, s: int, k: int, d: int, *, dtype: str = "f32",
    budget: int = VMEM_BUDGET_BYTES,
) -> list[tuple[int, int, int]]:
    """Hardware-aligned (block_s, block_k, block_d) triples under ``budget``.

    Every block divides the padded problem (ops.py pads to the chosen block),
    sublane-aligns block_s (8 for f32, 16 for bf16) and lane-aligns
    block_k/block_d (128).
    """
    sub = _SUBLANE[dtype]
    s_opts = [o for o in (sub, 64, 128, 256, 512, 1024) if o >= sub]
    k_opts = (128, 256)
    d_opts = (128, 256, 512, 1024)
    sp, kp, dp = _round_up(s, sub), _round_up(k, _LANE), _round_up(d, _LANE)
    out = []
    for bs in s_opts:
        if bs > sp and bs > sub:  # block bigger than the padded data
            continue
        for bk in k_opts:
            if bk > kp and bk != _LANE:
                continue
            for bd in d_opts:
                if bd > dp and bd != _LANE:
                    continue
                kt = _round_up(k, bk) if kernel == "lloyd" else None
                if vmem_bytes(kernel, bs, bk, bd, k_total=kt,
                              dtype=dtype) <= budget:
                    out.append((bs, bk, bd))
    return out


def cache_key(kernel: str, s: int, k: int, d: int, *, dtype: str = "f32",
              backend: str | None = None) -> str:
    if backend is None:
        import jax

        backend = jax.default_backend()
    return (f"{backend}/{kernel}/s{_bucket(s)}/k{_bucket(k)}"
            f"/d{_bucket(d)}/{dtype}")


# ---------------------------------------------------------------------------
# cache persistence
# ---------------------------------------------------------------------------


def _load(path: str) -> dict[str, dict]:
    global _mem_cache, _mem_cache_path
    with _lock:
        if _mem_cache is not None and _mem_cache_path == path:
            return _mem_cache
        entries: dict[str, dict] = {}
        try:
            with open(path, encoding="utf-8") as fh:
                raw = json.load(fh)
            if isinstance(raw, dict) and raw.get("version") == CACHE_VERSION:
                entries = dict(raw.get("entries", {}))
        except (OSError, ValueError):
            entries = {}  # missing or corrupt cache == empty cache
        _mem_cache, _mem_cache_path = entries, path
        return entries


def _store(path: str, key: str, blocks: tuple[int, int, int],
           us: float) -> None:
    with _lock:
        entries = dict(_mem_cache or {})
        entries[key] = {"blocks": list(blocks), "us": round(us, 1)}
        _set_mem(path, entries)
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump({"version": CACHE_VERSION, "entries": entries},
                          fh, indent=1, sort_keys=True)
                fh.write("\n")
            os.replace(tmp, path)
        except OSError:
            pass  # a read-only cache dir degrades to per-process memory


def _set_mem(path: str, entries: dict[str, dict]) -> None:
    global _mem_cache, _mem_cache_path
    _mem_cache, _mem_cache_path = entries, path


def invalidate_memory_cache() -> None:
    """Forget the in-process cache copy (tests / cache-path changes)."""
    global _mem_cache, _mem_cache_path
    with _lock:
        _mem_cache = None
        _mem_cache_path = None


# ---------------------------------------------------------------------------
# probing
# ---------------------------------------------------------------------------


def probe(
    make_call: Callable[[tuple[int, int, int]], Callable[[], object]],
    cands: Iterable[tuple[int, int, int]],
    *,
    reps: int = 3,
) -> Optional[tuple[tuple[int, int, int], float]]:
    """Time ``make_call(blocks)()`` for each candidate; return (winner, us),
    or None when no candidate ran.

    One un-timed warmup per candidate swallows compilation; the score is the
    median of ``reps`` timed calls. Candidates that fail to compile or run
    (e.g. an over-budget tile the estimate missed) are skipped; an error
    from ``make_call`` itself propagates.
    """
    import jax

    best: Optional[tuple[int, int, int]] = None
    best_us = float("inf")
    for blocks in cands:
        # Outside the try: a factory refuses to probe off the TPU, and that
        # must reach the caller rather than read as "no tile compiled".
        call = make_call(blocks)
        try:
            jax.block_until_ready(call())  # warmup / compile
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(call())
                ts.append((time.perf_counter() - t0) * 1e6)
            us = statistics.median(ts)
        # The factories run compiled kernels on the TPU only, so what this
        # skips is a tile the chip's compiler refuses (more VMEM or a layout
        # the estimate missed) or that fails at run time: not a winner.
        except Exception:  # noqa: BLE001
            continue
        if us < best_us:
            best, best_us = blocks, us
    return None if best is None else (best, best_us)


# Per-kernel probe-call factories are registered by ops.py (it owns the
# padded call convention); keys are kernel names.
_PROBE_FACTORIES: dict[str, Callable] = {}


def register_probe(kernel: str, factory: Callable) -> None:
    """factory(s, k, d, dtype, blocks) -> zero-arg timed callable."""
    _PROBE_FACTORIES[kernel] = factory


def lookup(
    kernel: str, s: int, k: int, d: int, *, dtype: str = "f32",
    backend: str | None = None,
) -> Optional[tuple[int, int, int]]:
    """Tuned (block_s, block_k, block_d) for this shape bucket, or None.

    Honors ``REPRO_AUTOTUNE``: 'off' -> always None (heuristics), 'on' ->
    cache consult only, 'probe' -> cache consult, then time candidates on a
    miss and persist the winner. Pure Python — safe to call at jit trace
    time (the probe path executes *compiled* kernels, which is legal during
    tracing, just slow the first time).
    """
    mode = flags.autotune_mode()
    if mode == "off":
        return None
    path = flags.autotune_cache_path()
    key = cache_key(kernel, s, k, d, dtype=dtype, backend=backend)
    hit = _load(path).get(key)
    if hit is not None:
        blocks = hit.get("blocks")
        if (isinstance(blocks, (list, tuple)) and len(blocks) == 3
                and all(isinstance(b, int) and b > 0 for b in blocks)):
            return tuple(blocks)  # type: ignore[return-value]
    if mode != "probe":
        return None
    factory = _PROBE_FACTORIES.get(kernel)
    if factory is None:
        return None
    # Probe at the bucketed shape so the persisted winner matches every
    # shape that maps to this key, not just the first one seen.
    sb, kb, db = _bucket(s), _bucket(k), _bucket(d)
    cands = candidates(kernel, sb, kb, db, dtype=dtype)
    if not cands:
        return None
    winner = probe(lambda b: factory(sb, kb, db, dtype, b), cands)
    if winner is None:
        return None
    blocks, us = winner
    _store(path, key, blocks, us)
    return blocks
