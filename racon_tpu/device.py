"""The device behind ``backend="tpu"``: one check, one identity.

``--tpu`` means a TPU.  Off a TPU the drivers would otherwise pick the
XLA twin, Pallas interpret mode or the host aligner and still exit 0, so
a run that never reached the chip looked like one that did.  Every
device-path entry point (``create_polisher(backend="tpu")``, the serve
daemon, distrib/fleet workers, ``bench.py``) therefore calls
:func:`require_tpu` first.  The one way to run the device path on a CPU
is to ask for it by name — ``JAX_PLATFORMS=cpu`` — which is how the
tests and the ``chip_smoke.py`` rehearsal run the interpreted kernels.

The identity (platform, device kind, count) and this process's
persistent-compilation-cache traffic go into every ``RunReport``, so a
CPU run and a chip run no longer produce the same report.
"""

from __future__ import annotations

import json
import os
import sys
import threading

_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
#: wraps compile_or_get_cached: seconds spent compiling an executable
#: or loading it from the persistent cache
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_lock = threading.Lock()
_cache_counts = {"requests": 0, "hits": 0, "misses": 0, "compile_s": 0.0}
_listening = False


class DeviceUnavailable(RuntimeError):
    """``backend="tpu"`` was asked for and this process has no TPU."""


def cpu_requested() -> bool:
    """True when the CPU backend was asked for by name
    (``JAX_PLATFORMS=cpu``, or the same value through ``jax.config``).
    A JAX that fell back to the CPU on its own does not count."""
    jax = sys.modules.get("jax")
    val = (jax.config.jax_platforms if jax is not None
           else os.environ.get("JAX_PLATFORMS"))
    return bool(val) and val.split(",")[0].strip().lower() == "cpu"


def _on_cache_event(event: str, **_kw) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is not None:
        with _lock:
            _cache_counts[key] += 1


def _on_compile(event: str, duration_secs: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        with _lock:
            _cache_counts["compile_s"] += duration_secs


def cache_traffic() -> dict:
    """Persistent-cache traffic of this process since the first
    :func:`require_tpu`: compile requests that consulted the cache,
    hits (executable loaded from disk), misses (compiled and written),
    and the seconds spent compiling or loading."""
    with _lock:
        return dict(_cache_counts)


def identity() -> dict:
    """The device as JAX reports it.  Initialises the backend."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> dict:
    """Identity of the device the ``tpu`` backend will run on; raises
    :class:`DeviceUnavailable` (one line) unless it is a TPU or the CPU
    was asked for by name."""
    global _listening
    import jax

    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_cache_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_compile)
            _listening = True
    try:
        ident = identity()
    except RuntimeError as e:
        raise DeviceUnavailable(
            "backend 'tpu': JAX could not initialise a device "
            f"({str(e).splitlines()[0] if str(e) else type(e).__name__})"
        ) from e
    if ident["platform"] != "tpu" and not cpu_requested():
        raise DeviceUnavailable(
            f"backend 'tpu' needs a TPU; JAX found {ident['count']} "
            f"{ident['platform']} device(s). Run without --tpu for the "
            "host path, or set JAX_PLATFORMS=cpu to rehearse the "
            "interpreted kernels on purpose")
    return ident


def check_device_workers(n_workers: int, what: str) -> None:
    """Refuse more than one ``backend="tpu"`` worker process on this
    host.  A JAX process claims every local chip (workers are not pinned
    to chips yet), so a second device worker fails or hangs at start-up
    — and used to fall to interpreted kernels or the host instead.
    Workers on a CPU asked for by name hold no chip and may be many."""
    if n_workers > 1 and not cpu_requested():
        raise DeviceUnavailable(
            f"{what}: {n_workers} backend='tpu' worker processes on one "
            "host, but each claims every local chip; run one device "
            "worker (or backend='cpu' workers)")


if __name__ == "__main__":
    # `python -m racon_tpu.device`: the identity as one JSON line, or one
    # line of why not and exit 1 — how an orchestrator that must stay
    # off JAX (chip_smoke.py, bench.py) asks which device a child gets
    try:
        print(json.dumps(require_tpu()))
    except DeviceUnavailable as e:
        sys.exit(str(e))
