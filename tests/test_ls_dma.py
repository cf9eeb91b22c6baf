"""DMA-semaphore accounting of the lockstep POA kernel, under the TPU
interpreter.

Plain interpret mode runs a DMA wait as a no-op, so a wait with no
matching start — which never returns on the chip — passes every other
test in the suite.  ``pltpu.InterpretParams`` models the semaphores (a
wait blocks until its DMA was started) and fills scratch with NaN
instead of zeros.  The ``ls`` tier's first chip run hung on exactly
this: the H-ring spill waited twice on one chunk whenever a layer's DP
ended on a 64-rank boundary, and waited on a chunk the layer never
flushed whenever its DP started past rank 64.  This batch holds one grid
program of each kind, at one sublane group a program and at two (the
spill, the traceback loads and the layer copies carry the group axis);
the kernel runs in a child process so that a deadlock is a failed test,
not a hung suite.
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import random
import numpy as np
from jax.experimental.pallas import tpu as pltpu
from racon_tpu.ops import poa, poa_pallas_ls
from racon_tpu.ops.encoding import encode

cfg = poa.PoaConfig(max_nodes=512, max_len=256, max_backbone=256,
                    max_edges=12, depth=4, match=5, mismatch=-4, gap=-8)
U = __GROUPS__
W, BLK = U * poa_pallas_ls.G, poa_pallas_ls.BLK
B = 2 * W
rng = random.Random(3)
bb = np.zeros((B, cfg.max_backbone), np.uint8)
bbw = np.zeros((B, cfg.max_backbone), np.int32)
bb_len = np.ones(B, np.int32)
nl = np.zeros(B, np.int32)
seqs = np.zeros((B, cfg.depth, cfg.max_len), np.uint8)
ws = np.zeros((B, cfg.depth, cfg.max_len), np.int32)
lens = np.zeros((B, cfg.depth), np.int32)
bg = np.zeros((B, cfg.depth), np.int32)
en = np.zeros((B, cfg.depth), np.int32)


def put(b, backbone, layers, begin, end):
    bb[b, :len(backbone)] = encode(np.frombuffer(backbone, np.uint8))
    bb_len[b] = len(backbone)
    nl[b] = len(layers)
    for i, lay in enumerate(layers):
        seqs[b, i, :len(lay)] = encode(np.frombuffer(lay, np.uint8))
        ws[b, i, :len(lay)] = 1
        lens[b, i] = len(lay)
        bg[b, i], en[b, i] = begin, end


for b in range(B):
    if b < W:
        # perfect full-span reads: the graph stays 2*BLK ranks, so every
        # layer's DP ends exactly on a chunk boundary with two chunks
        truth = bytes(rng.choice(b"ACGT") for _ in range(2 * BLK))
        put(b, truth, [truth] * 3, 0, len(truth) - 1)
    else:
        # every layer of the program starts past the first chunk; its
        # first window's layers carry one base the backbone lacks, so a
        # node is inserted at rank 150 of 200 and the slot arrays are
        # shifted, a block of slots a step, under the interpreter too
        truth = bytes(rng.choice(b"ACGT") for _ in range(200))
        extra = bytes([next(c for c in b"ACGT"
                            if c not in truth[149:151])]) if b == W else b""
        put(b, truth, [truth[100:150] + extra + truth[150:]] * 3, 100, 199)

interp = pltpu.InterpretParams(dma_execution_mode="on_wait",
                               uninitialized_memory="nan")
ls = poa_pallas_ls.build_lockstep_poa_kernel(cfg, interpret=interp,
                                             groups=U)(B)
cb, cc, cl, fl, nn, counts = (np.asarray(x) for x in ls(
    bb_len[:, None], nl[:, None], lens, bg, en, bb.astype(np.int32), bbw,
    seqs.astype(np.int32), ws))
# perfect reads: the chain's one in-edge plus one, a group a layer; the
# inserted node's successor holds two in-edges from the second layer on,
# one slot more twice (the scalar is written under uninitialised-memory
# NaNs like everything else)
got = dict(zip(poa_pallas_ls.PROGRAM_COUNTS, counts.T.tolist()))
swept = got.pop("slots_swept")
assert swept == [2 * U * 3, 2 * U * 3 + 2], swept
# the kernel's own step counts accumulate in that SMEM output, which
# starts as garbage here: three layers a program; the first program's
# DP runs ranks 0 .. 127, the second's 64 .. 199 (a pair a trip, from
# the chunk its layers start in; 201 ranks once the node is in) and
# its traceback walks down from block 3; a read's length of update
# steps a layer; one insertion, in one block of slots; a scan trip a
# rank of a layer's span past its first (the chain edge, at distance 1:
# 127 of 128 ranks, 99 of the 100 from rank 100), and once the node is
# in 101 ranks and one trip more where the chain edge spans it
assert got == {"steps.dp": [3 * 128, 136 + 2 * 138],
               "steps.traceback": [3 * 2 * BLK, 3 * 4 * BLK],
               "steps.update": [3 * 128, 3 * 101],
               "insert.firings": [0, 1],
               "insert.shift_steps": [0, 1],
               "steps.dp_scan": [3 * 127, 99 + 2 * 101],
               "steps.tb_scan": [3 * 127, 99 + 2 * 101]}, got
jb, jc, jl, jf, jn = (np.asarray(x) for x in poa.build_poa_kernel(cfg)(
    bb, bbw, bb_len, nl, seqs, ws, lens, bg, en))
assert not fl.any() and not jf.any(), (fl.ravel(), jf.ravel())
for b in range(B):
    n = int(cl[b, 0])
    assert n == int(jl[b]) and int(nn[b, 0]) == int(jn[b]), b
    np.testing.assert_array_equal(cb[b, :n], jb[b, :n])
    np.testing.assert_array_equal(cc[b, :n], jc[b, :n])
print("ls == xla under the TPU interpreter")
"""


@pytest.mark.parametrize("groups", [1, 2, 4], ids=["u1", "u2", "u4"])
def test_lockstep_spill_semaphores_balance_under_tpu_interpreter(groups):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (ROOT, os.environ.get("PYTHONPATH")) if p))
    env.pop("XLA_FLAGS", None)   # one device: the child shards nothing
    try:
        r = subprocess.run([sys.executable, "-c",
                            _CHILD.replace("__GROUPS__", str(groups))], env=env,
                           capture_output=True, text=True, timeout=420)
    except subprocess.TimeoutExpired:
        raise AssertionError(
            "lockstep kernel deadlocked under the TPU interpreter: a DMA "
            "wait without a matching start (it would hang the chip)")
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ls == xla" in r.stdout
