"""Topology-keyed memoization for kernel builders.

A plain ``functools.lru_cache`` on a kernel builder is a latent bug: the
built object bakes in the device set (sharding meshes, interpret-mode
decisions), so reconfiguring JAX devices after a first build would serve
a stale sharded/interpreted kernel (the round-5 ADVICE finding on
``_build_kernel_cached``).  ``device_keyed_cache`` is the sanctioned
replacement: it appends ``(len(jax.devices()), platform)`` to the cache
key implicitly, keeping builder signatures unchanged.

The ``kernel-cache-key`` lint rule (racon_tpu/analysis) enforces that
every cached kernel builder either uses this decorator or takes explicit
``n_dev`` + ``platform`` parameters.
"""

from __future__ import annotations

import functools
import time

from .. import fingerprint, obs


def device_keyed_cache(maxsize: int = 64):
    """`functools.lru_cache` whose key implicitly includes the device
    topology (device count + platform) at call time.

    Exposes ``cache_clear`` / ``cache_info`` like lru_cache.  jax is
    imported lazily at first call so decorated builders stay importable
    before any backend configuration (e.g. the test suite's forced CPU
    mesh)."""
    def deco(build):
        @functools.lru_cache(maxsize=maxsize)
        def cached(_n_dev, _platform, *args, **kwargs):
            return build(*args, **kwargs)

        @functools.wraps(build)
        def wrapper(*args, **kwargs):
            import jax

            devs = jax.devices()
            # Kernel-(re)build observability: a cache miss here is the
            # builder running (tracing + staging; the XLA compile proper
            # lands in the first submit span).  The miss is only known
            # after the call, so the span is stamped retroactively from
            # monotonic stamps taken around it.
            misses0 = cached.cache_info().misses
            t0 = time.monotonic_ns()
            # the implicit topology prefix is the `kernel_cache` site of
            # the unified fingerprint registry (racon_tpu/fingerprint.py)
            topo = fingerprint.kernel_cache_key(len(devs),
                                                devs[0].platform)
            built = cached(*topo, *args, **kwargs)
            if cached.cache_info().misses != misses0:
                obs.add_complete("kernel.build", t0, time.monotonic_ns(),
                                 builder=build.__name__,
                                 platform=devs[0].platform)
                obs.count(f"kernel.builds.{build.__name__}")
            # Opt-in runtime sanitizer (RACON_TPU_SANITIZE=1): hand the
            # built kernel back wrapped in a checking proxy. Imported
            # lazily at call time — by the first kernel build the
            # analysis package is safe to import, while a module-level
            # import here would run analysis/__init__ during ops import.
            from ..analysis import sanitize

            if sanitize.enabled():
                return sanitize.wrap_kernel(build.__name__, built)
            return built

        wrapper.cache_clear = cached.cache_clear
        wrapper.cache_info = cached.cache_info
        wrapper.__wrapped__ = build
        return wrapper
    return deco
