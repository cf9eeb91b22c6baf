"""Count the scheduled VLIW bundles of `racon_hirschberg_base`'s loops
without a chip: compile the kernel for a described v5e with libtpu's LLO
dumps on, and read the final bundles back.

A loop body's bundle count is a floor on its cycles a trip, not its
time: the stalls of a serial chain come on top (the one-task traceback
step of PR 50 was 70-75 bundles and ran several times that).  Scalar
loads and stores to `*_spill` slots show scalar register pressure.  A
time comes from a chip run only.

Usage: python racon_tpu/tools/kernel_bundles.py [K ...]   (default: BANDS)
"""

import glob
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def compile_for_v5e(K):
    """(child) one grid program of the base kernel at band K, compiled
    ahead of time; libtpu writes the dumps and then aborts the process
    for want of a report template, which the parent expects."""
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from racon_tpu.ops import align_pallas

    jax.config.update("jax_enable_compilation_cache", False)
    kern, _, qcap, tcap = align_pallas._build_base_kernel(K, False)
    fn = kern(align_pallas.GROUP)
    device = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
    specs = [jax.ShapeDtypeStruct((align_pallas.GROUP, w), np.int32,
                                  sharding=SingleDeviceSharding(device))
             for w in (4, qcap, tcap)]
    jax.jit(getattr(fn, "body", fn)).lower(*specs).compile()


def loops(path):
    """-> [(bundles, spill loads and stores)] of each loop of a
    final_bundles dump: from a bundle marked LB to the branch back."""
    found, start, spills = [], None, 0
    for line in open(path):
        m = re.match(r"\s*(0x[0-9a-f]+)\s*(LB|PF)?:?", line)
        if not m:
            continue
        if m.group(2) == "LB":
            start, spills = int(m.group(1), 16), 0
        if start is not None:
            spills += len(re.findall(r"s(?:ld|st) \[smem:\[#\w+_spill", line))
            if "sbr.rel" in line:
                found.append((int(m.group(1), 16) - start, spills))
                start = None
    return found


def main(argv):
    if argv[:1] == ["--compile"]:
        return compile_for_v5e(int(argv[1]))
    from racon_tpu.ops.align_pallas import BANDS

    for K in [int(a) for a in argv] or BANDS:
        with tempfile.TemporaryDirectory() as out:
            env = dict(os.environ, JAX_PLATFORMS="cpu", RACON_TPU_SHARD="0",
                       TPU_LOG_DIR="disabled", LIBTPU_INIT_ARGS=(
                           f"--xla_jf_dump_to={out} "
                           "--xla_jf_dump_llo_text=true"))
            subprocess.run([sys.executable, __file__, "--compile", str(K)],
                           env=env, stdin=subprocess.DEVNULL, timeout=600,
                           stdout=subprocess.DEVNULL,
                           stderr=subprocess.DEVNULL)
            dumps = [p for p in glob.glob(os.path.join(
                out, "*racon_hirschberg_base*final_bundles.txt"))
                if "schedule-analysis" not in p]
            if not dumps:
                print(f"K={K}: no dump (the compile failed)")
                continue
            # the forward DP's loop comes first, the traceback's second
            print(f"K={K}: " + ", ".join(
                f"{name} {n} bundles a trip ({s} spill sld/sst)"
                for name, (n, s) in zip(("dp", "walk"), loops(dumps[0]))))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
