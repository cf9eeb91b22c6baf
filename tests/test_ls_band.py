"""Nightly end-to-end band for the production ls kernel tier.

The shipped consensus kernel (ls, the lane-lockstep Pallas kernel) must
be exercised end to end on real data recurringly — otherwise a
regression in the ls driver plumbing would surface only via the
component differentials (the quick suite's interpret λ band runs the XLA
twin, tests/test_golden.py). Reference analogue: the upstream suite
runs its accelerator path over the same λ goldens as the CPU path
(/root/reference/test/racon_test.cpp:297-507).

The λ polish runs in a FRESH subprocess on a 1-device CPU backend: under
this suite's 8-virtual-device mesh the interpret-mode ls run exceeds
25 minutes, while single-device it takes ~200 s (docs/benchmarks.md —
measured 2026-07-30: edit distance 1282, 92/96 windows device-served).
Gated behind RACON_TPU_FULL_GOLDEN=1, so it rides the nightly
full-golden CI job rather than the per-push quick job.
"""

import json
import os
import subprocess
import sys

import pytest

from tests.conftest import DATA, requires_data

FULL = os.environ.get("RACON_TPU_FULL_GOLDEN") == "1"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = requires_data

_CHILD = """
import json, os, sys
sys.path.insert(0, %(repo)r)
from __graft_entry__ import _force_cpu
_force_cpu(1)                      # 1-device mesh: escapes the suite's 8
os.environ["RACON_TPU_PALLAS"] = "1"   # interpret-mode pallas on CPU
# the batch decides the program's width (poa_driver._group_width): 64, a
# TPU's batch, runs programs of sixteen; 8 the program of eight
os.environ["RACON_TPU_BATCH_WINDOWS"] = %(batch)r

import gzip
from racon_tpu import native
from racon_tpu.pipeline import Pipeline
from racon_tpu.ops.align_driver import run_alignment_phase
from racon_tpu.ops.poa_driver import run_consensus_phase
from racon_tpu.tools import golden_scenarios as gs

D = %(data)r
reads, ovl, tgt, extra = gs.POLISH["paf"]
args = dict(gs.ARGS, **extra)
pipe = Pipeline(D + reads, D + ovl, D + tgt, **args)
pipe.prepare()
run_alignment_phase(pipe)
pipe.build_windows()
stats = run_consensus_phase(pipe, match=args["match"],
                            mismatch=args["mismatch"], gap=args["gap"],
                            trim=True)
res = pipe.stitch(True)
assert len(res) == 1, len(res)

ref = b"".join(l.strip().encode()
               for l in gzip.open(D + "sample_reference.fasta.gz", "rt")
               if not l.startswith(">"))
pol = res[0][1].encode()
rc = pol.translate(bytes.maketrans(b"ACGT", b"TGCA"))[::-1]
counters = {k: v for k, v in stats.items() if isinstance(v, int)}
print("RESULT " + json.dumps({"ed": native.edit_distance(rc, ref),
                              "stats": counters}))
"""


@pytest.mark.skipif(not FULL, reason="~200 s single-device interpret run; "
                    "set RACON_TPU_FULL_GOLDEN=1 (nightly band)")
@pytest.mark.parametrize("batch", ["64", "8"], ids=["u2", "u1"])
def test_ls_tier_lambda_end_to_end_band(batch):
    child = _CHILD % {"repo": REPO, "data": DATA, "batch": batch}
    r = subprocess.run([sys.executable, "-c", child], capture_output=True,
                       text=True, timeout=1800, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, r.stdout[-2000:]
    out = json.loads(line[-1][len("RESULT "):])
    ed, stats = out["ed"], out["stats"]

    # same band the quick suite pins for the twin; the measured ls
    # value is 1282 (host pin 1283)
    assert abs(ed - 1283) <= 15, (ed, stats)
    # the ls tier must actually SERVE: 92/96 windows measured, with 4
    # repeat-dense windows through the per-window host fallback — a
    # silent degrade to host (stats device ~0) must fail here
    assert stats["device"] >= 88, stats
    assert stats["device"] + stats["host_fallback"] + stats["backbone"] \
        >= 96, stats
