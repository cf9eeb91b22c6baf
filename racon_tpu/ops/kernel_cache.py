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

:class:`Program` is what such a builder hands back: the one place a
kernel body meets ``jax.jit``, and the persistent **program cache** in
front of it.  Tracing a kernel body and lowering it (Pallas -> Mosaic)
is a pure function of the kernel's source, its geometry and the JAX
version, costs seconds a program and is not covered by JAX's compile
cache, so its result, the ``jax.export`` serialisation of the lowered
program, is kept in ``<compile cache dir>/programs/`` and loaded by the
next process.
"""

from __future__ import annotations

import functools
import hashlib
import os
import threading
import time

from .. import device, fingerprint, obs

#: A program lowered for one of these platforms is kept on disk; any
#: other (interpret mode and the XLA twin on a CPU) is a plain
#: ``jax.jit``, as ``interp = platform != "tpu"`` decides for the
#: kernels themselves.
PERSISTED_PLATFORMS = ("tpu",)
_MAGIC = b"racon-tpu-program-1\n"
_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def programs_dir():
    """Where lowered programs are kept: ``programs/`` under JAX's
    compile cache directory, or None where that is unset or "" (the
    opt-out covers both caches)."""
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    return os.path.join(cache_dir, "programs") if cache_dir else None


@functools.lru_cache(maxsize=1)
def _source_digest() -> str:
    # once a process: the modules it runs were read at import
    return fingerprint.kernel_source_digest(_PACKAGE_DIR)


def _environment():
    """What a lowered program depends on besides its builder's arguments
    and input shapes: the topology, the lowering libraries' versions and
    the kernel sources' digest."""
    import jax
    import jaxlib

    devs = jax.devices()
    topology = (len(devs), devs[0].platform, devs[0].device_kind)
    versions = (jax.__version__, jaxlib.__version__,
                devs[0].client.platform_version,
                jax.config.jax_export_calling_convention_version)
    return topology, versions, _source_digest()


def _read_program(path, sig, platform):
    """The exported program in `path`, or None for a miss: no such file,
    a file that is not whole (magic, digest), bytes ``jax.export``
    refuses, or a program other than the one asked for."""
    import jax
    import jax.export

    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return None
    head = len(_MAGIC) + hashlib.sha256().digest_size
    payload = blob[head:]
    if (blob[:len(_MAGIC)] != _MAGIC
            or blob[len(_MAGIC):head] != hashlib.sha256(payload).digest()):
        return None
    try:
        exported = jax.export.deserialize(bytearray(payload))
    except Exception:  # noqa: BLE001 — whatever a blob of another JAX raises is a miss, never an error
        return None
    same = (tuple(exported.platforms) == (platform,)
            and exported.calling_convention_version
            == jax.config.jax_export_calling_convention_version
            and tuple((a.shape, a.dtype) for a in exported.in_avals) == sig)
    return exported if same else None


def _write_program(path, payload: bytes) -> None:
    """Whole or not at all: a temporary name in the same directory, then
    ``os.replace``.  Racing writers write the same program and the last
    one wins; a directory that cannot be written costs the next process
    a miss, nothing else."""
    tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(_MAGIC + hashlib.sha256(payload).digest() + payload)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


class Program:
    """A kernel program: ``body`` behind ``jax.jit`` (with `shardings`,
    an ``(in_shardings, out_shardings)`` pair, for a program over a
    mesh), and behind the program cache where `key` (the builder's name
    and arguments) is given, a compile cache directory is set and the
    platform is one of ``PERSISTED_PLATFORMS``.

    There the first call with given input shapes looks for
    ``<programs_dir>/<fingerprint.program_key>.jaxexp``; a miss traces
    and lowers ``body`` through ``jax.export`` and writes the result.
    Either way what runs is ``jax.jit`` of the exported program's
    ``call`` under the body's name, so the process that lowered and the
    process that loaded hand XLA the same module and ask the compile
    cache for the same entry.  Called on tracers (under ``shard_map`` or
    another ``jit``) the body is inlined into the caller's program."""

    def __init__(self, body, key=None, shardings=None):
        self.body, self.key, self.shardings = body, key, shardings
        self.__name__ = getattr(body, "__name__", "program")
        self._plain = self._jit(body)
        self._calls = {}
        self._lock = threading.Lock()

    def _jit(self, fn):
        import jax

        if self.shardings is None:
            return jax.jit(fn)
        return jax.jit(fn, in_shardings=self.shardings[0],
                       out_shardings=self.shardings[1])

    def __call__(self, *args):
        sig = tuple((a.shape, a.dtype) for a in args)
        call = self._calls.get(sig)
        if call is None:
            call = self._resolve(sig, args)
        return call(*args)

    def lower(self, *specs):
        """The body's own lowering (ahead-of-time compiles for a
        described topology): no cache in front of it."""
        return self._plain.lower(*specs)

    def _resolve(self, sig, args):
        import jax

        if any(isinstance(a, jax.core.Tracer) for a in args):
            return self._plain
        with self._lock:
            call = self._calls.get(sig)
            if call is None:
                call = self._calls[sig] = self._load_or_export(sig)
        return call

    def _load_or_export(self, sig):
        import jax
        import jax.export

        directory = programs_dir()
        if self.key is None or directory is None:
            return self._plain
        topology, versions, source = _environment()
        platform = topology[1]
        if platform not in PERSISTED_PLATFORMS:
            return self._plain
        sig = tuple((shape, jax.dtypes.canonicalize_dtype(dtype))
                    for shape, dtype in sig)
        path = os.path.join(directory, fingerprint.program_key(
            self.key, [(shape, dtype.name) for shape, dtype in sig],
            topology, versions, source) + ".jaxexp")
        t0 = time.monotonic()
        with obs.span("kernel.program.load", fun=self.__name__):
            exported = _read_program(path, sig, platform)
        if exported is not None:
            device.count_program("hit", time.monotonic() - t0)
            return self.run_exported(exported)
        try:
            exported = jax.export.export(self._plain, platforms=[platform])(
                *(jax.ShapeDtypeStruct(*a) for a in sig))
            payload = bytes(exported.serialize())
        except Exception:  # noqa: BLE001 — a program jax.export refuses still runs: the plain jit raises what a real lowering fault raises
            device.count_program("skipped")
            return self._plain
        _write_program(path, payload)
        device.count_program("miss")
        return self.run_exported(exported)

    def run_exported(self, exported):
        """``jax.jit`` of an exported program's ``call`` under the body's
        name and this program's shardings: what runs, whether `exported`
        was just lowered or read from the cache."""
        @device.named(self.__name__)
        def run(*args):
            return exported.call(*args)

        return self._jit(run)


def body_and_key(fn):
    """A Program's body and key, for a caller that wraps it into a
    larger program (``shard_map``, sharding constraints); a bare
    function is its own body and has no key."""
    return getattr(fn, "body", fn), getattr(fn, "key", None)


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
