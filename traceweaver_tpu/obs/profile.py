"""JAX profiler hooks behind the ``TW_PROFILE`` knob.

Three pieces, all inert by default:

- :func:`annotate` — the one way a profiler span is opened: a
  ``jax.profiler.TraceAnnotation`` when profiling is on (``TW_PROFILE=1``,
  or the caller's own reading of it), a null context otherwise, and jax
  is never imported then.
- :func:`stage` — the one timer of a host stage. It bills the stage's
  seconds to its stats key, stamps the stage on the self-trace of the
  windows it carries, and, when its caller runs with ``TW_PROFILE=1``,
  wraps the stage in an :func:`annotate` span named by the caller's
  prefix and the stage (the fleet's ``tw:fleet:<stage>``): the
  profiler's own host trace, on the device trace's clock, so every
  device idle gap can be put down to the stage the host was in.
- :func:`device_memory_families` — scrape-time gauge families over
  ``device.memory_stats()`` (bytes in use / limit per device), merged
  into ``/metrics`` when ``TW_PROFILE=1``; devices/backends without the
  hook report nothing rather than raising mid-scrape.
"""

from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional, Tuple

from traceweaver_tpu.obs import selftrace as _selftrace
from traceweaver_tpu.runtime import knobs as _knobs


def enabled() -> bool:
    """``TW_PROFILE`` (typed registry read, call time — the knob can
    flip between two solves without a reimport)."""
    return _knobs.get_bool("TW_PROFILE")


def annotate(name: str, on: Optional[bool] = None, **args):
    """Named profiler span ``jax.profiler.TraceAnnotation(name, **args)``
    when ``on`` (default: ``TW_PROFILE``, read now); a null context
    otherwise."""
    if on is None:
        on = enabled()
    if not on:
        return nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name, **args)


class StageTime:
    """What :func:`stage` measured. ``less`` (set by the body) is time
    inside the stage billed elsewhere, subtracted from the stats key;
    ``seconds`` is what was billed, set on a normal exit."""

    __slots__ = ("less", "seconds")

    def __init__(self) -> None:
        self.less = 0.0
        self.seconds = 0.0


@contextmanager
def stage(st, name: str, key: Optional[str] = None, trace_keys=(),
          trace_as: Optional[str] = None, **args):
    """Time one host stage: the one way a fleet stage is timed.

    ``st`` is the caller's stats accumulator: ``st.add(key, seconds)``
    bills the stage, ``st.profile`` is ``TW_PROFILE`` as read once for
    the caller's call, and ``st.span_prefix`` names its spans. On a
    normal exit the stage's seconds (less ``StageTime.less``) go to
    ``key`` when one is given, and the stage is stamped on the
    self-trace of every window in ``trace_keys`` as ``trace_as``
    (default ``name``) when a tracer is active. Under ``st.profile`` the
    body runs inside the span ``annotate(st.span_prefix + name,
    **args)``; the clock is read inside it, so the annotation's own cost
    is never billed. A body that raises bills nothing."""
    t = StageTime()
    with annotate(st.span_prefix + name, st.profile, **args):
        t0 = time.perf_counter()
        yield t
        dt = time.perf_counter() - t0
    t.seconds = dt - t.less
    if key is not None:
        st.add(key, t.seconds)
    if trace_keys:
        tracer = _selftrace.active()
        if tracer is not None:
            w1 = _selftrace.now_us()
            for k in trace_keys:
                tracer.stage(k, trace_as or name, w1 - dt * 1e6, w1)


def device_memory_families() -> List[Tuple[str, str, str,
                                           List[Tuple[Dict[str, str],
                                                      float]]]]:
    """Collector-style gauge families of per-device memory stats
    (``TW_PROFILE=1``; empty otherwise, and empty on backends whose
    devices expose no ``memory_stats``)."""
    if not enabled():
        return []
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return []
    samples: List[Tuple[Dict[str, str], float]] = []
    for dev in devices:
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        label_dev = f"{dev.platform}:{dev.id}"
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if key in stats:
                samples.append(({"device": label_dev, "kind": key},
                                float(stats[key])))
    if not samples:
        return []
    return [("tw_device_memory_bytes", "gauge",
             "per-device memory stats (TW_PROFILE=1; device.memory_stats)",
             samples)]
