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
CPU run and a chip run no longer produce the same report.  The same
listener times the stages of making a program (trace, lower, compile) by
the jitted function's name, and :func:`named` is how a kernel's wrapper
gets the name that clock, the HLO module and a profile's device ops show.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

from . import obs

_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
#: the stages of making a program, as ``jax.monitoring`` times them:
#: tracing the Python function to a jaxpr, lowering the jaxpr to an MLIR
#: module (Pallas -> Mosaic happens here), and compile_or_get_cached
#: (compiling an executable or loading it from the persistent cache)
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": ("trace", "traces"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lower",
                                                        "lowerings"),
    "/jax/core/compile/backend_compile_duration": ("compile", None),
}
_lock = threading.Lock()
_cache_counts = {"requests": 0, "hits": 0, "misses": 0, "compile_s": 0.0,
                 "trace_s": 0.0, "lower_s": 0.0, "traces": 0,
                 "lowerings": 0, "program_hits": 0, "program_misses": 0,
                 "program_skipped": 0, "program_load_s": 0.0}
_PROGRAM_OUTCOMES = {"hit": "program_hits", "miss": "program_misses",
                     "skipped": "program_skipped"}
_by_fun: dict = {}
#: a stage shorter than this is counted but gets no span: eager
#: primitives trace in microseconds, by the thousand under interpret mode
_SPAN_FLOOR_S = 1e-3
_making = threading.local()      # .depth: stages open on this thread
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


def _on_stage_start(event: str, _value, **_kw) -> None:
    # JAX records a stage's start time as a scalar under the stage's own
    # event name; a JAX that stops doing so leaves depth at 0 and nested
    # stages are then counted too (tests/test_launch_spans.py notices)
    if event in _STAGES:
        _making.depth = getattr(_making, "depth", 0) + 1


def _on_stage(event: str, duration_secs: float, fun_name="?",
              **_kw) -> None:
    """One finished jit stage: into the process's totals, and, when
    ``obs`` is armed, onto the job's timeline as a retroactive span
    (from a millisecond up).  A trace or a lowering that ran while
    another stage was open on the thread is left out (Pallas lowering
    traces the kernel's jnp helpers by the thousand): the outer stage's
    duration already holds its time.  ``compile_s`` stays what it was,
    the sum of every ``backend_compile`` event, nested or not."""
    stage, counter = _STAGES.get(event, (None, None))
    if stage is None:
        return
    _making.depth = depth = max(getattr(_making, "depth", 1) - 1, 0)
    if depth and stage != "compile":
        return
    # lowering and compiling name the module, "jit(<function>)"
    fun = str(fun_name)
    if fun.startswith("jit(") and fun.endswith(")"):
        fun = fun[4:-1]
    with _lock:
        _cache_counts[f"{stage}_s"] += duration_secs
        row = _by_fun.setdefault(fun, {"trace_s": 0.0, "lower_s": 0.0,
                                       "compile_s": 0.0, "n": 0})
        row[f"{stage}_s"] += duration_secs
        if counter is not None:
            _cache_counts[counter] += 1
        if stage == "lower":
            row["n"] += 1
    if stage == "trace":
        obs.count("jit.traces")     # in a window job: a retrace
    if duration_secs >= _SPAN_FLOOR_S:
        now = time.monotonic_ns()
        obs.add_complete(f"jit.{stage}", now - int(duration_secs * 1e9),
                         now, fun=fun)


def count_program(outcome: str, load_s: float = 0.0) -> None:
    """One kernel program resolved against the program cache
    (``ops/kernel_cache.Program``): a ``hit`` (read from disk and
    deserialized in ``load_s`` seconds), a ``miss`` (traced, lowered and
    written) or ``skipped`` (``jax.export`` refused it; it runs as a
    plain ``jax.jit``).  Into the process's totals and, when ``obs`` is
    armed, the job's counters."""
    with _lock:
        _cache_counts[_PROGRAM_OUTCOMES[outcome]] += 1
        _cache_counts["program_load_s"] += load_s
    obs.count(f"kernel.program.{outcome}")


def named(name: str):
    """Give a kernel's jitted wrapper the stable name its program goes
    by everywhere: the HLO module (``jit_<name>``), the device ops of a
    profile, ``jax.monitoring``'s ``fun_name``.  One name per kernel
    kind, not per geometry (shapes are in the op text already); set
    before ``jax.jit`` / ``shard_map`` sees the function."""
    def deco(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn
    return deco


def named_like(local):
    """The per-shard kernel `local` behind a plain function carrying its
    name, for ``shard_map``: the sharded program is then ``jit_racon_*``
    like the single-device one, not ``jit__lambda_``."""
    def sharded(*a):
        return local(*a)
    return named(getattr(local, "__name__", "sharded"))(sharded)


def cache_traffic() -> dict:
    """What making programs cost this process since the first
    :func:`require_tpu`: compile requests that consulted the persistent
    cache, hits (executable loaded from disk), misses (compiled and
    written), the seconds spent compiling or loading (``compile_s``),
    tracing (``trace_s``, over ``traces`` outermost traces) and lowering
    (``lower_s``, ``lowerings``), and the same seconds by the jitted
    function's name (``by_fun``; ``n`` = programs lowered); kernel
    programs loaded from the program cache (``program_hits``, in
    ``program_load_s`` seconds of read + deserialize), derived and
    written to it (``program_misses``) and refused by ``jax.export``
    (``program_skipped``).  Process
    lifetime, so work outside any job's tracer (``warm_for_target``) is
    covered."""
    with _lock:
        return {**_cache_counts,
                "by_fun": {f: dict(row) for f, row in _by_fun.items()}}


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
            jax.monitoring.register_scalar_listener(_on_stage_start)
            jax.monitoring.register_event_duration_secs_listener(
                _on_stage)
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
