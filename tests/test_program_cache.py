"""The persistent program cache in front of every kernel's ``jax.jit``
(``ops/kernel_cache.Program``): a lowered program is exported once, kept
under ``<compile cache dir>/programs/`` and loaded by the next process.

CPU only.  On the CPU the cache is off by the platform's decision
(``kernel_cache.PERSISTED_PLATFORMS``); the tests that drive the
mechanism end to end switch it on for the ``cpu`` platform, in the test
and not through an option of the program.
"""

import json
import os
import shutil
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.export

from racon_tpu import device, fingerprint, obs
from racon_tpu.ops import align_pallas, kernel_cache, poa
from racon_tpu.ops.kernel_cache import Program
from racon_tpu.parallel import get_partitioner, reset_partitioner

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "racon_tpu")
RCAP, K = 512, 256          # the smallest Hirschberg edge bucket


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """A compile cache directory of the test's own, with the program
    cache switched on for the CPU the tests run on."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jc"))
    monkeypatch.setattr(kernel_cache, "PERSISTED_PLATFORMS", ("tpu", "cpu"))
    device.require_tpu()                      # the listeners of cache_traffic
    yield str(tmp_path / "jc" / "programs")
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", before)


def _blobs(directory):
    return sorted(os.listdir(directory)) if os.path.isdir(directory) else []


def _traffic():
    t = device.cache_traffic()
    return {k: t[k] for k in ("program_hits", "program_misses",
                              "program_skipped")}


def _delta(before):
    return {k: v - before[k] for k, v in _traffic().items()}


def _edge_args(B, seed=0):
    rng = np.random.default_rng(seed)
    qin = max(128, (RCAP // 4 + 127) // 128 * 128)
    scal = np.zeros((B, 4), np.int32)
    scal[:, 0] = scal[:, 1] = rng.integers(50, 200, B)
    return (scal, rng.integers(0, 4, (B, qin)).astype(np.int32),
            rng.integers(0, 4, (B, RCAP + K)).astype(np.int32))


def _edge_program(B, backward=False):
    """A fresh Program of the interpreted edge kernel, as a new process
    would build it (the builders' in-process caches dropped)."""
    align_pallas._build_edge_kernel.cache_clear()
    return align_pallas._build_edge_kernel(RCAP, K, backward, True)(B)


def _twin():
    import __graft_entry__ as g

    cfg = poa.PoaConfig(max_nodes=256, max_len=128, max_backbone=64,
                        max_edges=8, depth=4)
    args = g._example_batch(cfg, 2, np.random.default_rng(1))
    poa.build_poa_kernel.cache_clear()
    return poa.build_poa_kernel(cfg), args


@pytest.fixture
def single_device(monkeypatch):
    monkeypatch.setenv("RACON_TPU_SHARD", "0")
    reset_partitioner()
    yield
    reset_partitioner()


# -- round trips --------------------------------------------------------------

@pytest.mark.parametrize("which", ["xla_twin", "hirschberg_interpret"])
def test_round_trip_gives_equal_outputs(cache_dir, single_device, which):
    """Miss (export, write), then a fresh Program of the same key hits
    (read, deserialize): both run the exported program and agree with
    the plain ``jax.jit`` of the body."""
    def build():
        if which == "xla_twin":
            return _twin()
        return _edge_program(8), _edge_args(8)

    before = _traffic()
    prog, args = build()
    cold = jax.tree.map(np.asarray, prog(*args))
    assert _delta(before) == {"program_hits": 0, "program_misses": 1,
                              "program_skipped": 0}
    assert len(_blobs(cache_dir)) == 1
    prog, args = build()
    warm = jax.tree.map(np.asarray, prog(*args))
    assert _delta(before) == {"program_hits": 1, "program_misses": 1,
                              "program_skipped": 0}
    plain = jax.tree.map(np.asarray, prog._plain(*args))
    for a, b, c in zip(jax.tree.leaves(cold), jax.tree.leaves(warm),
                       jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    # one aval signature, one resolve: the second call asks nothing
    prog(*args)
    assert _delta(before)["program_hits"] == 1


@pytest.mark.parametrize("kernel", ["racon_poa_ls", "hirschberg_edge"])
def test_tpu_lowered_program_survives_the_file(tmp_path, single_device,
                                               kernel):
    """What the chip's processes keep: the Pallas -> Mosaic lowering for
    the TPU, as ``test_tpu_lowering._export_tpu`` makes it, through the
    cache's own file format and back with platform and avals intact."""
    import test_tpu_lowering as lowering

    if kernel == "racon_poa_ls":
        prog, args = lowering._ls(500, 32, lowering.SHARD_BATCH)
    else:
        prog, args = lowering._edge(RCAP, K, False, 8)
    assert isinstance(prog, Program) and prog.key is not None
    sig = tuple((a.shape, a.dtype) for a in args)
    exported = jax.export.export(prog._plain, platforms=["tpu"])(
        *(jax.ShapeDtypeStruct(*a) for a in sig))
    path = str(tmp_path / "p" / "one.jaxexp")
    kernel_cache._write_program(path, bytes(exported.serialize()))
    back = kernel_cache._read_program(path, sig, "tpu")
    assert back is not None
    assert tuple(back.platforms) == ("tpu",)
    assert tuple((a.shape, a.dtype) for a in back.in_avals) == sig
    assert back.mlir_module_serialized == exported.mlir_module_serialized
    assert back.fun_name == prog.__name__
    # the kernel's name travels inside the module (the device ops and
    # the roofline readers match on it)
    assert prog.__name__.encode() in bytes(back.mlir_module_serialized)
    # asked for as another platform's or other shapes' program: a miss
    assert kernel_cache._read_program(path, sig, "cpu") is None
    assert kernel_cache._read_program(path, sig[:-1], "tpu") is None


def test_sharded_program_round_trips_under_the_mesh(cache_dir, monkeypatch):
    """``shard_build`` over four virtual devices: the exported program
    is a four-device one and runs, from the file too, under the mesh's
    NamedShardings, with the single-device program's output."""
    monkeypatch.setenv("RACON_TPU_MESH_SHAPE", "4")
    reset_partitioner()
    try:
        part = get_partitioner()
        assert part.batch_axis_size == 4
        args = _edge_args(16)
        before = _traffic()
        outs = []
        for _ in range(2):
            prog = _edge_program(16)
            assert prog.key[0] == "shard_map" and prog.shardings
            out = prog(*args)
            assert out.sharding.is_equivalent_to(
                part.sharding("windows"), out.ndim)
            outs.append(np.asarray(out))
        assert _delta(before) == {"program_hits": 1, "program_misses": 1,
                                  "program_skipped": 0}
        monkeypatch.setenv("RACON_TPU_SHARD", "0")
        single = _edge_program(16)
        assert single.shardings is None
        np.testing.assert_array_equal(outs[0], np.asarray(single(*args)))
        np.testing.assert_array_equal(outs[0], outs[1])
        assert len(_blobs(cache_dir)) == 2    # sharded and single differ
    finally:
        reset_partitioner()
        align_pallas._build_edge_kernel.cache_clear()


# -- the key ------------------------------------------------------------------

CFG = poa.PoaConfig()
KEY = dict(builder=("racon_poa_ls", CFG, False, False, 2, 64),
           avals=(((64, 1), "int32"),),
           topology=(1, "tpu", "TPU v5 lite"),
           versions=("0.9.0", "0.9.0", "libtpu x", 10),
           source="0" * 64)


@pytest.mark.parametrize("field,value", [
    ("builder", ("racon_poa_ls", CFG._replace(max_edges=13), False, False,
                 2, 64)),                                   # a PoaConfig field
    ("builder", ("racon_poa_ls", CFG, False, False, 2, 16)),    # the batch
    ("builder", ("racon_poa_ls", CFG, False, False, 4, 64)),    # group width
    ("topology", (1, "tpu", "TPU v6 lite")),                    # device kind
    ("topology", (4, "tpu", "TPU v5 lite")),                    # device count
    ("versions", ("0.9.1", "0.9.0", "libtpu x", 10)),           # JAX version
    ("versions", ("0.9.0", "0.9.0", "libtpu x", 9)),   # calling convention
    ("avals", (((64, 1), "uint8"),)),
], ids=["poa_config_field", "batch", "group_width", "device_kind",
        "device_count", "jax_version", "calling_convention", "avals"])
def test_key_changes_with(field, value):
    assert (fingerprint.program_key(**{**KEY, field: value})
            != fingerprint.program_key(**KEY))
    assert fingerprint.program_key(**KEY) == fingerprint.program_key(**KEY)


def _copy_sources(dest):
    for sub in ("ops", "parallel"):
        shutil.copytree(os.path.join(PACKAGE, sub), os.path.join(dest, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(PACKAGE, "device.py"), dest)
    return dest


def test_key_changes_with_one_source_byte_and_not_with_the_path(tmp_path):
    here = fingerprint.kernel_source_digest(PACKAGE)
    a = _copy_sources(str(tmp_path / "a" / "racon_tpu"))
    b = _copy_sources(str(tmp_path / "elsewhere" / "deeper" / "pkg"))
    assert fingerprint.kernel_source_digest(a) == here
    assert fingerprint.kernel_source_digest(b) == here
    victim = os.path.join(b, "ops", "align_pallas.py")
    src = open(victim, "rb").read()
    at = src.index(b"GROUP = 8")
    with open(victim, "wb") as f:
        f.write(src[:at] + b"GROUP = 9" + src[at + 9:])
    edited = fingerprint.kernel_source_digest(b)
    assert edited != here
    assert (fingerprint.program_key(**{**KEY, "source": edited})
            != fingerprint.program_key(**{**KEY, "source": here}))
    # a file outside the kernel sources does not move it
    with open(os.path.join(a, "polisher.py"), "w") as f:
        f.write("x = 1\n")
    assert fingerprint.kernel_source_digest(a) == here


def test_an_edited_kernel_file_is_not_served_the_old_program(
        cache_dir, single_device, tmp_path, monkeypatch):
    """The hazard the digest rules out: a blob of the old source under a
    process that runs the new one."""
    args = _edge_args(8)
    _edge_program(8)(*args)
    assert len(_blobs(cache_dir)) == 1
    pkg = _copy_sources(str(tmp_path / "pkg"))
    with open(os.path.join(pkg, "ops", "band.py"), "ab") as f:
        f.write(b"\n")
    monkeypatch.setattr(kernel_cache, "_PACKAGE_DIR", pkg)
    kernel_cache._source_digest.cache_clear()
    try:
        before = _traffic()
        _edge_program(8)(*args)
        assert _delta(before) == {"program_hits": 0, "program_misses": 1,
                                  "program_skipped": 0}
        assert len(_blobs(cache_dir)) == 2
    finally:
        monkeypatch.undo()
        kernel_cache._source_digest.cache_clear()


# -- blobs that are not the program -------------------------------------------

def _other_convention(payload_of):
    """A whole file holding a valid program exported under the other
    calling convention this JAX supports."""
    now = jax.config.jax_export_calling_convention_version
    other = (jax.export.minimum_supported_calling_convention_version
             if now != jax.export.minimum_supported_calling_convention_version
             else jax.export.maximum_supported_calling_convention_version)
    assert other != now
    jax.config.update("jax_export_calling_convention_version", other)
    try:
        exported = payload_of()
    finally:
        jax.config.update("jax_export_calling_convention_version", now)
    assert exported.calling_convention_version == other
    return bytes(exported.serialize())


@pytest.mark.parametrize("damage", ["truncated", "garbage", "empty",
                                    "bad_payload", "other_convention"])
def test_a_bad_blob_is_a_miss_that_rebuilds_and_overwrites(
        cache_dir, single_device, tmp_path, damage):
    args = _edge_args(8)
    prog = _edge_program(8)
    want = np.asarray(prog(*args))
    (name,) = _blobs(cache_dir)
    path = os.path.join(cache_dir, name)
    good = open(path, "rb").read()
    if damage == "truncated":
        bad = good[:len(good) // 2]
    elif damage == "garbage":
        bad = os.urandom(len(good))
    elif damage == "empty":
        bad = b""
    elif damage == "bad_payload":
        # whole by its digest, refused by jax.export.deserialize
        kernel_cache._write_program(path, b"not a flatbuffer" * 64)
        bad = open(path, "rb").read()
    else:
        sig = tuple((a.shape, a.dtype) for a in args)
        assert kernel_cache._read_program(path, sig, "cpu") is not None
        kernel_cache._write_program(path, _other_convention(
            lambda: jax.export.export(prog._plain, platforms=["cpu"])(
                *(jax.ShapeDtypeStruct(*a) for a in sig))))
        bad = open(path, "rb").read()
        assert bad != good
    with open(path, "wb") as f:
        f.write(bad)
    before = _traffic()
    got = np.asarray(_edge_program(8)(*args))
    np.testing.assert_array_equal(got, want)
    assert _delta(before) == {"program_hits": 0, "program_misses": 1,
                              "program_skipped": 0}
    assert _blobs(cache_dir) == [name]
    # overwritten with a whole program (its bytes are the first one's up
    # to the call sites in its locations), which is a hit again
    sig = tuple((a.shape, a.dtype) for a in args)
    assert open(path, "rb").read() != bad
    assert kernel_cache._read_program(path, sig, "cpu") is not None
    _edge_program(8)(*args)
    assert _delta(before)["program_hits"] == 1


def test_concurrent_writers_leave_one_whole_file(tmp_path):
    """More writers than cores on one name, readers meanwhile: a reader
    sees no file or a whole one, never a part, and no temporary name
    stays behind."""
    exported = jax.export.export(jax.jit(lambda x: x * 2 + 1),
                                 platforms=["cpu"])(
        jax.ShapeDtypeStruct((4096,), np.int32))
    payload = bytes(exported.serialize())
    sig = (((4096,), np.dtype(np.int32)),)
    path = str(tmp_path / "programs" / "k.jaxexp")
    stop = threading.Event()
    torn, errors = [], []

    def write():
        try:
            for _ in range(30):
                kernel_cache._write_program(path, payload)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    def read():
        try:
            while not stop.is_set():
                if (os.path.exists(path)
                        and kernel_cache._read_program(path, sig,
                                                       "cpu") is None):
                    torn.append(1)
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        writers = [threading.Thread(target=write)
                   for _ in range(2 * (os.cpu_count() or 4))]
        readers = [threading.Thread(target=read) for _ in range(2)]
        for t in readers + writers:
            t.start()
        for t in writers:
            t.join(timeout=120)
        stop.set()
        for t in readers:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
        stop.set()
    assert not any(t.is_alive() for t in writers + readers)
    assert not errors and not torn
    assert os.listdir(os.path.dirname(path)) == ["k.jaxexp"]
    back = kernel_cache._read_program(path, sig, "cpu")
    assert back.mlir_module_serialized == exported.mlir_module_serialized


# -- engagement and counters ---------------------------------------------------

@pytest.mark.parametrize("why", ["no_cache_dir", "platform_not_persisted",
                                 "no_key"])
def test_nothing_is_written_where_the_cache_does_not_engage(
        cache_dir, single_device, monkeypatch, why):
    """``JAX_COMPILATION_CACHE_DIR=""`` opts out of both caches; a CPU
    program is the platform's plain ``jax.jit``; a Program without a key
    (a test's lambda under ``shard_build``) is never kept."""
    if why == "no_cache_dir":
        # what JAX makes of JAX_COMPILATION_CACHE_DIR="" at start-up
        jax.config.update("jax_compilation_cache_dir", "")
        assert kernel_cache.programs_dir() is None
    elif why == "platform_not_persisted":
        monkeypatch.setattr(kernel_cache, "PERSISTED_PLATFORMS", ("tpu",))
    args = _edge_args(8)
    prog = _edge_program(8)
    if why == "no_key":
        prog = Program(prog.body)
    before = _traffic()
    out = np.asarray(prog(*args))
    np.testing.assert_array_equal(out, np.asarray(prog._plain(*args)))
    assert _delta(before) == {"program_hits": 0, "program_misses": 0,
                              "program_skipped": 0}
    assert _blobs(cache_dir) == []


def test_an_empty_cache_dir_in_the_environment_means_no_program_cache():
    code = ("import racon_tpu, jax; "
            "from racon_tpu.ops import kernel_cache; "
            "print(repr(jax.config.jax_compilation_cache_dir), "
            "kernel_cache.programs_dir())")
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": "",
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split()[-1] == "None", out.stdout


def test_counters_and_cache_traffic_agree(cache_dir, single_device):
    obs.reset()
    obs.configure(metrics=True)
    try:
        before = device.cache_traffic()
        args = _edge_args(8)
        _edge_program(8)(*args)                     # miss
        _edge_program(8)(*args)                     # hit
        _edge_program(8, backward=True)(*args)      # miss
        after = device.cache_traffic()
        snap = obs.snapshot()
        counters = snap["counters"]
        assert counters["kernel.program.miss"] == 2
        assert counters["kernel.program.hit"] == 1
        assert "kernel.program.skipped" not in counters
        assert after["program_misses"] - before["program_misses"] == 2
        assert after["program_hits"] - before["program_hits"] == 1
        assert after["program_load_s"] > before["program_load_s"]
        # one load span a resolve, found or not
        loads = snap["histograms"]["span_us.kernel.program.load"]
        assert loads["count"] == 3
        # a build is still a build, counted where it was
        assert counters["kernel.builds._build_edge_kernel"] == 3
    finally:
        obs.reset()


def test_a_program_jax_export_refuses_runs_as_a_plain_jit(cache_dir):
    """Counted, never an error: the body runs through ``jax.jit`` and a
    real lowering fault would be raised there as it is today."""
    def body(x):
        return jax.pure_callback(lambda v: v + 1,
                                 jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    before = _traffic()
    prog = Program(device.named("racon_test_callback")(body),
                   key=("racon_test_callback",))
    np.testing.assert_array_equal(
        np.asarray(prog(np.arange(4, dtype=np.int32))), np.arange(1, 5))
    assert _delta(before) == {"program_hits": 0, "program_misses": 0,
                              "program_skipped": 1}
    assert _blobs(cache_dir) == []


def test_program_under_a_trace_is_inlined(cache_dir, single_device):
    """Called on tracers the body joins the caller's program: nothing is
    looked up, and ``lower`` is the body's own."""
    prog = _edge_program(8)
    args = _edge_args(8)
    before = _traffic()
    out = jax.jit(lambda *a: prog(*a) + 1)(*args)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(prog._plain(*args)) + 1)
    assert "racon_hirschberg_edge_fwd" in prog.lower(*args).as_text()
    assert _delta(before) == {"program_hits": 0, "program_misses": 0,
                              "program_skipped": 0}


# -- a second process ----------------------------------------------------------

_CHILD = """
import json, sys
import numpy as np
from racon_tpu import device
from racon_tpu.ops import align_pallas, kernel_cache, poa_driver
kernel_cache.PERSISTED_PLATFORMS = ("tpu", "cpu")
device.require_tpu()
rng = np.random.default_rng(0)
scal = np.zeros((8, 4), np.int32); scal[:, :2] = 100
q = rng.integers(0, 4, (8, 128)).astype(np.int32)
t = rng.integers(0, 4, (8, 768)).astype(np.int32)
outs = []
for backward in (False, True):
    fn = align_pallas._build_edge_kernel(512, 256, backward, True)(8)
    outs.append(int(np.asarray(fn(scal, q, t)).astype(np.int64).sum()))
base, _, qcap, tcap = align_pallas._build_base_kernel(256, True)
ops, cnt, ok, dist = base(8)(scal, np.resize(q, (8, qcap)),
                             np.resize(t, (8, tcap)))
outs.append(int(np.asarray(dist).astype(np.int64).sum()))
traffic = device.cache_traffic()
traffic["outs"] = outs
print("TRAFFIC " + json.dumps(traffic))
"""


def test_a_second_process_loads_what_the_first_lowered(tmp_path):
    """Two processes over one directory: the first lowers three programs
    and writes them, the second reads them, lowers no kernel body (one
    trace and one lowering a program: the one-call wrapper's) and asks
    the compile cache for the entries the first one wrote."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "RACON_TPU_SHARD": "0",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jc")}

    def run():
        out = subprocess.run([sys.executable, "-c", _CHILD], cwd=REPO,
                             env=env, capture_output=True, text=True,
                             timeout=600)
        assert out.returncode == 0, out.stderr[-4000:]
        line = next(ln for ln in out.stdout.splitlines()
                    if ln.startswith("TRAFFIC "))
        return json.loads(line[len("TRAFFIC "):])

    cold, warm = run(), run()
    assert (cold["program_misses"], cold["program_hits"]) == (3, 0)
    assert (warm["program_misses"], warm["program_hits"]) == (0, 3)
    assert warm["program_skipped"] == cold["program_skipped"] == 0
    assert warm["outs"] == cold["outs"]
    # the first process traced each body (for the export) and each
    # wrapper; the second only the wrappers
    assert cold["lowerings"] == 6 and warm["lowerings"] == 3
    assert cold["traces"] == 6 and warm["traces"] == 3
    assert warm["program_load_s"] > 0
    # the same modules went to XLA: the second process compiled nothing
    assert cold["misses"] == 3 and cold["hits"] == 0
    assert warm["misses"] == 0 and warm["hits"] == 3
    assert len(os.listdir(tmp_path / "jc" / "programs")) == 3
