"""Test configuration.

JAX runs on a virtual 8-device CPU mesh so multi-chip sharding compiles and
executes in CI without TPU hardware (the driver separately dry-runs the
multi-chip path; see __graft_entry__.py). Must be set before jax imports.

Set RACON_TPU_HW_TESTS=1 to NOT force the CPU mesh and run against the real
TPU backend instead — this enables the exact on-hardware pins (e.g. the λ
device golden in test_golden.py) and is only meant for a machine with a
TPU attached.

The persistent compilation cache is pointed outside the checkout (unless
the environment already names one), so a test run does not grow the tree
the chip tool has to copy; child processes inherit it.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      "/tmp/racon_tpu_test_jax_cache")

HW_TESTS = os.environ.get("RACON_TPU_HW_TESTS") == "1"

if not HW_TESTS:
    from __graft_entry__ import _force_cpu  # noqa: E402 (imports numpy only)

    _force_cpu(8)


def _assert_cpu_mesh():
    # Fail loudly if the forcing didn't take (e.g. a plugin initialized the
    # backend first) — otherwise tests would claim the real chip.
    import jax

    devs = jax.devices()
    assert devs[0].platform == "cpu" and len(devs) >= 8, (
        f"expected >=8 virtual CPU devices, got {len(devs)} "
        f"{devs[0].platform} — backend initialized before conftest?")


if not HW_TESTS:
    _assert_cpu_mesh()

import gzip  # noqa: E402

import pytest  # noqa: E402

# The reference lambda-phage dataset; override for CI environments without
# the reference checkout.
DATA = os.environ.get("RACON_TPU_TEST_DATA", "/root/reference/test/data/")

requires_data = pytest.mark.skipif(
    not os.path.isdir(DATA),
    reason=f"lambda test data not found at {DATA} "
           "(set RACON_TPU_TEST_DATA)")

def pytest_collection_modifyitems(config, items):
    if not HW_TESTS:
        return
    skip = pytest.mark.skip(
        reason="RACON_TPU_HW_TESTS=1: virtual 8-device CPU mesh disabled; "
               "multi-device tests need the default (forced-CPU) mode")
    for item in items:
        if "multichip" in item.nodeid or "multidevice" in item.nodeid:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _clear_kernel_cache():
    """poa_driver._build_kernel is memoized (warm-up's compiled kernel is
    the measured run's function object); tests that monkeypatch the
    kernel builders to inject failures must not see another test's real
    cached kernel, so drop the cache after every test."""
    yield
    try:
        from racon_tpu.ops import poa_driver

        poa_driver._build_kernel_cached.cache_clear()
    except Exception:  # noqa: BLE001 — package may not be importable yet
        pass
    try:
        # the memoized Partitioner carries sticky sharded->single-device
        # demotion state; a test that trips it must not demote the rest
        # of the suite
        from racon_tpu.parallel import reset_partitioner

        reset_partitioner()
    except Exception:  # noqa: BLE001
        pass
    try:
        # stop the mem-watchdog and drop latched watermark state so a
        # test that armed a tight RACON_TPU_MEM_BUDGET_MB cannot leave
        # hard-latched pressure (or a sampler thread) for the next test
        from racon_tpu.resilience import budget

        budget.reset()
    except Exception:  # noqa: BLE001
        pass


_COMP = bytes.maketrans(b"ACGT", b"TGCA")


def revcomp(s: bytes) -> bytes:
    return s.translate(_COMP)[::-1]


def read_fasta_gz(path):
    out = []
    name, chunks = None, []
    with gzip.open(path, "rt") as f:
        for line in f:
            line = line.strip()
            if line.startswith(">"):
                if name is not None:
                    out.append((name, "".join(chunks)))
                name = line[1:].split()[0]
                chunks = []
            else:
                chunks.append(line)
    if name is not None:
        out.append((name, "".join(chunks)))
    return out


@pytest.fixture(scope="session")
def lambda_reference() -> bytes:
    recs = read_fasta_gz(DATA + "sample_reference.fasta.gz")
    assert len(recs) == 1
    return recs[0][1].encode()
