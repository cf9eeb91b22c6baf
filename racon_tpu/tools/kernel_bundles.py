"""Count the scheduled VLIW bundles of a kernel's loops without a chip:
compile the kernel for a described v5e with libtpu's LLO dumps on, and
read the final bundles back.

A loop body's bundle count is a floor on its cycles a trip, not its
time: the stalls of a serial chain come on top (the one-task traceback
step of PR 50 was 70-75 bundles and ran several times that).  Scalar
loads and stores to `*_spill` slots show scalar register pressure.  A
time comes from a chip run only.

Usage: python racon_tpu/tools/kernel_bundles.py [K ...]   (default: BANDS)
           `racon_hirschberg_base` at band K: its two loops
       python racon_tpu/tools/kernel_bundles.py --kernel ls \
           [--window 500] [--depth 200] [--groups 4] [--rung 0]
           `racon_poa_ls` (a batch of 64): the loops of LS_LOOPS by name
"""

import argparse
import contextlib
import glob
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: `racon_poa_ls`'s loops as the compiler leaves them, by their place in
#: the loop tree (the indices of a walk from the grid loop down: the
#: layer loop is the grid loop's first child, the DP's rank pairs the
#: layer loop's first, ...).  A count takes in the loops inside it: the
#: pair holds both ranks' delta scans, a traceback rank its mscan, the
#: update step every group's insertion block and the two slot scans.
LS_LOOPS = (
    ("dp_pair", (0, 0)), ("delta_scan", (0, 0, 0)),
    ("tb_rank", (0, 2, 0)), ("mscan", (0, 2, 0, 0)),
    ("update_step", (0, 3)), ("insert_shift", (0, 3, 0)),
    ("score_rank", (1,)),
)
LS_BATCH = 64


def compile_base_for_v5e(K):
    """(child) one grid program of the base kernel at band K, compiled
    ahead of time; libtpu writes the dumps and then aborts the process
    for want of a report template, which the parent expects."""
    import numpy as np

    from racon_tpu.ops import align_pallas

    kern, _, qcap, tcap = align_pallas._build_base_kernel(K, False)
    _compile(kern(align_pallas.GROUP),
             [np.zeros((align_pallas.GROUP, w), np.int32)
              for w in (4, qcap, tcap)])


def compile_ls_for_v5e(window, depth, groups, rung):
    """(child) `racon_poa_ls` at a batch of LS_BATCH, as
    tests/test_tpu_lowering.py builds it."""
    import numpy as np

    import __graft_entry__ as g
    from racon_tpu.ops import poa_driver, poa_pallas_ls

    cfg = poa_driver.make_config(window, depth, 5, -4, -8, rung)
    fn = poa_pallas_ls.build_lockstep_poa_kernel(
        cfg, interpret=False, groups=groups)(LS_BATCH)
    bb, bbw, bl, nl, seqs, ws, lens, bg, en = g._example_batch(
        cfg, LS_BATCH, np.random.default_rng(0))
    _compile(fn, (bl.reshape(-1, 1), nl.reshape(-1, 1), lens, bg, en,
                  bb.astype(np.int32), bbw, seqs.astype(np.int32), ws))


def _compile(fn, args):
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    device = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype,
                                  sharding=SingleDeviceSharding(device))
             for a in args]
    # the body, not the Program: a count never writes the program cache
    jax.jit(getattr(fn, "body", fn)).lower(*specs).compile()


def loop_tree(path):
    """-> the loops of a final_bundles dump as a forest.  A bundle's
    line carries one '>' a loop it lies in, and a loop's body starts at
    the bundle marked LB: a loop is that bundle and every one after it
    at its depth or deeper.  Each loop: `bundles`, `ops` (instructions in
    them), `xlane` (cross-lane adds, vadd.xlane: an int32 lane sum is two, one
    a 16-bit half, a narrow one is one), `thin` (bundles holding at most one op),
    `vspill` and `sspill` (vector and scalar loads and stores to spill
    slots), all of them with the loops inside it, and `inner`, those
    loops in order."""
    roots, open_loops = [], []
    for line in open(path):
        m = re.match(r"\s*0x[0-9a-f]+\s*(LB)?\s*:\s*(>*) *\{(.*)", line)
        if not m:
            continue
        # an empty bundle carries no marks: it stays where the last one was
        depth = (len(m.group(2)) if m.group(2) or not open_loops
                 or not m.group(3).startswith("}") else open_loops[-1]["depth"])
        while open_loops and (open_loops[-1]["depth"] > depth or (
                m.group(1) and open_loops[-1]["depth"] == depth)):
            open_loops.pop()
        if m.group(1):
            loop = dict(depth=depth, bundles=0, ops=0, xlane=0, thin=0,
                        vspill=0, sspill=0, inner=[])
            (open_loops[-1]["inner"] if open_loops else roots).append(loop)
            open_loops.append(loop)
        ops = [op for op in m.group(3).split(";;") if "=" in op]
        for loop in open_loops:
            loop["bundles"] += 1
            loop["ops"] += len(ops)
            loop["thin"] += len(ops) <= 1
            loop["xlane"] += sum("vadd.xlane" in op for op in ops)
            for kind, space in (("v", "vmem"), ("s", "smem")):
                loop[kind + "spill"] += sum(bool(re.search(
                    rf"{kind}(?:ld|st) \[{space}:\[#\w+_spill", op))
                    for op in ops)
    return roots


def named_loops(roots, names):
    """-> {name: loop} for (name, path) in `names`, a path the child
    indices from the first root down; a name whose path the tree lacks
    is left out."""
    found = {}
    for name, path in names:
        level, loop = roots[:1], None
        for i in (0,) + tuple(path):
            if i >= len(level):
                loop = None
                break
            loop = level[i]
            level = loop["inner"]
        if loop is not None:
            found[name] = loop
    return found


@contextlib.contextmanager
def _dump(kernel, child_args):
    """The loops of `kernel`'s final bundles (loop_tree), from a child
    that compiles it as `child_args` say; None where it left no dump."""
    with tempfile.TemporaryDirectory() as out:
        env = dict(os.environ, JAX_PLATFORMS="cpu", RACON_TPU_SHARD="0",
                   TPU_LOG_DIR="disabled", LIBTPU_INIT_ARGS=(
                       f"--xla_jf_dump_to={out} "
                       "--xla_jf_dump_llo_text=true"))
        subprocess.run([sys.executable, __file__, "--compile"] + child_args,
                       env=env, stdin=subprocess.DEVNULL, timeout=600,
                       stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        dumps = [p for p in glob.glob(os.path.join(
            out, f"*{kernel}*final_bundles.txt"))
            if "schedule-analysis" not in p]
        yield loop_tree(dumps[0]) if dumps else None


def main(argv):
    p = argparse.ArgumentParser()
    p.add_argument("--kernel", choices=("base", "ls"), default="base")
    p.add_argument("--compile", action="store_true")   # the child's mark
    p.add_argument("--window", type=int, default=500)
    p.add_argument("--depth", type=int, default=200)
    p.add_argument("--groups", type=int, default=4)
    p.add_argument("--rung", type=int, default=0)
    p.add_argument("bands", nargs="*", type=int)
    a = p.parse_args(argv)
    if a.compile:
        if a.kernel == "ls":
            return compile_ls_for_v5e(a.window, a.depth, a.groups, a.rung)
        return compile_base_for_v5e(a.bands[0])
    if a.kernel == "ls":
        label = (f"ls window={a.window} depth={a.depth} groups={a.groups} "
                 f"rung={a.rung}")
        with _dump("racon_poa_ls", argv) as roots:   # the same geometry
            if roots is None:
                print(f"{label}: no dump (the compile failed)")
                return
            found = named_loops(roots, LS_LOOPS)
        for name, _ in LS_LOOPS:
            if name not in found:
                print(f"{label}: {name} not found")
                continue
            print(f"{label}: {name} " + "{bundles} bundles a trip, {ops} "
                  "ops, {xlane} cross-lane adds, {thin} bundles of at most "
                  "one op, {vspill} vector spill ld/st".format(**found[name]))
        return
    from racon_tpu.ops.align_pallas import BANDS

    for K in a.bands or BANDS:
        with _dump("racon_hirschberg_base", [str(K)]) as roots:
            if roots is None:
                print(f"K={K}: no dump (the compile failed)")
                continue
            # the forward DP's loop comes first, the traceback's second
            print(f"K={K}: " + ", ".join(
                "{} {bundles} bundles a trip ({sspill} spill sld/sst)".format(
                    name, **loop)
                for name, loop in zip(("dp", "walk"), roots)))


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
