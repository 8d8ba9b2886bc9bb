"""Profiler integration.

The reference's only tracing is the Timer stage's wall-clock logging
(ref: src/pipeline-stages/src/main/scala/Timer.scala:54); SURVEY §5 marks
jax-profiler/xplane integration as the intended TPU upgrade. Any stage
(Timer's ``traceDir``, TPULearner's ``profileDir``) can wrap its hot
section in ``maybe_trace`` to emit a TensorBoard-loadable xplane trace of
the real device timeline. Host stages reach the same file through
``core.trace.phase``.
"""

from __future__ import annotations

import contextlib
import glob
import os
from typing import Iterator, List, Optional


@contextlib.contextmanager
def maybe_trace(trace_dir: Optional[str]) -> Iterator[None]:
    """jax.profiler.trace(trace_dir) when a directory is given, else a
    no-op — callers wrap unconditionally and the param decides."""
    if not trace_dir:
        yield
        return
    import jax
    os.makedirs(trace_dir, exist_ok=True)
    with jax.profiler.trace(trace_dir):
        yield


def trace_files(trace_dir: str) -> List[str]:
    """The xplane protobufs a trace run produced (for tests/tools)."""
    return sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))


def device_memory_stats(device=None) -> Optional[dict]:
    """Device 0's (or ``device``'s) ``memory_stats()`` as a plain dict,
    or None when the backend doesn't report them (CPU) or jax isn't
    loaded — safe to call from exporters at any time (a /metrics
    scrape must not be the thing that pays jax's import + backend
    init in a process that never touched it)."""
    import sys
    if "jax" not in sys.modules:
        return None
    try:
        import jax
        d = device if device is not None else jax.local_devices()[0]
        stats = d.memory_stats()
    except Exception:  # noqa: BLE001 — no backend / no stats: no sample
        return None
    return dict(stats) if stats else None


def mesh_memory_stats() -> Optional[dict]:
    """Memory stats summed across EVERY local device — the mesh-wide
    pressure signal sharded serving needs (a model sharded over 8
    chips spends HBM on all 8; watching device 0 alone misses 7/8 of
    the footprint). ``bytes_in_use``/``bytes_limit``/``peak_bytes_in_use``
    sum; ``per_device`` keeps the individual ``bytes_in_use`` readings
    so an imbalanced placement is visible. Same safety contract as
    ``device_memory_stats`` (None when the backend doesn't report)."""
    import sys
    if "jax" not in sys.modules:
        return None
    try:
        import jax
        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — no backend: no sample
        return None
    total: dict = {}
    per_device: dict = {}
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — device without stats
            stats = None
        if not stats:
            continue
        for key in ("bytes_in_use", "bytes_limit", "peak_bytes_in_use"):
            if key in stats:
                total[key] = total.get(key, 0) + int(stats[key])
        per_device[str(d)] = int(stats.get("bytes_in_use", 0))
    if not total:
        return None
    total["devices"] = len(per_device)
    total["per_device"] = per_device
    return total
