"""Measure on-hardware λ device goldens: run golden scenarios through the
TPU backend (fused Pallas kernel) on the real chip and print the exact
accuracy numbers to pin.

The reference pins its accelerator goldens next to the CPU ones for every
scenario (/root/reference/test/racon_test.cpp:297-507 — 10 GPU pins); this
tool produces the numbers pinned the same way in
racon_tpu/tools/golden_scenarios.py (asserted by tests/test_golden.py
under RACON_TPU_HW_TESTS=1).

Usage:  python racon_tpu/tools/pin_device_golden.py [scenario|all]
Scenarios: paf (default) | sam | sam_noq | paf_noq | paf_w1000 | unit
           | kc | kf_fasta | kf_paf | all
"""

import gzip
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import racon_tpu
from racon_tpu import config, native
from racon_tpu.tools import golden_scenarios as gs

# same dataset location + override knob as tests/conftest.py (not imported:
# this tool must not inherit the test suite's CPU-mesh forcing)
DATA = config.get_str("RACON_TPU_TEST_DATA")

# The device pins isolate the CONSENSUS device path: phase 1 runs on the
# host aligner unless the caller overrides. The existing paf=1282 pin was
# measured under the host aligner (2026-07-29, before 'auto' defaulted
# phase 1 to hirschberg-on-TPU); pinning the engine here keeps every
# refresh comparable to it. Hirschberg-phase-1 accuracy is chip_smoke.py's
# edit-distance verdict, not these pins.
os.environ.setdefault("RACON_TPU_DEVICE_ALIGNER", "host")

ARGS = gs.ARGS  # single source: the args the asserted pins are defined by

COMP = bytes.maketrans(b"ACGT", b"TGCA")


def revcomp(b: bytes) -> bytes:
    return b.translate(COMP)[::-1]


def run_scenario(name: str, ref: bytes):
    if name in gs.POLISH:
        reads, ovl, tgt, extra = gs.POLISH[name]
        kind = "polish"
    else:
        reads, ovl, tgt, extra = gs.FRAGMENT[name]
        kind = "fragment"
    args = dict(ARGS)
    extra = dict(extra)
    drop = extra.pop("drop", True)
    args.update(extra)
    t0 = time.time()
    p = racon_tpu.create_polisher(DATA + reads, DATA + ovl, DATA + tgt,
                                  backend="tpu", **args)
    p.initialize()
    res = p.polish(drop)
    dt = time.time() - t0
    if kind == "polish":
        assert len(res) == 1, len(res)
        ed = native.edit_distance(revcomp(res[0][1].encode()), ref)
        return f"{name}: device_golden_ed={ed} wall={dt:.1f}s"
    count = len(res)
    total = sum(len(d) for _, d in res)
    return f"{name}: device_golden=({count}, {total}) wall={dt:.1f}s"


def main():
    scenario = sys.argv[1] if len(sys.argv) > 1 else "paf"
    known = list(gs.POLISH) + list(gs.FRAGMENT)
    if scenario != "all" and scenario not in known:
        sys.exit(f"unknown scenario {scenario!r}; one of {known} or 'all'")

    with gzip.open(DATA + "sample_reference.fasta.gz", "rb") as f:
        ref = b"".join(line.strip() for line in f
                       if not line.startswith(b">"))

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        # a CPU/interpret-mode number must never be mistaken for the
        # hardware golden
        sys.exit(f"refusing to measure: platform is {platform!r}, not tpu")
    aligner = config.get_raw("RACON_TPU_DEVICE_ALIGNER")
    print(f"platform={platform} kernel_tier=ls aligner={aligner}")

    names = known if scenario == "all" else [scenario]
    for name in names:
        print(run_scenario(name, ref), flush=True)


if __name__ == "__main__":
    main()
