"""The λ-phage golden scenario table, shared by tests/test_golden.py and
pin_device_golden.py so the pinned numbers and the tool that measures them
can never drift apart.

The reference pins accelerator accuracy next to the CPU numbers for every
scenario (/root/reference/test/racon_test.cpp:297-507: 6 polish scenarios
plus fragment-correction kC/kF, 10 GPU pins total); this table carries the
same inventory for the TPU path. HOST pins are asserted unconditionally in
CI; DEVICE pins are asserted on real hardware (RACON_TPU_HW_TESTS=1) and
measured/refreshed with:

    python racon_tpu/tools/pin_device_golden.py <scenario>|all

A device pin of None means "not yet measured on a healthy chip" — the
hardware test reports it as a skip, never a pass.
"""

# base polisher arguments every pin is measured (and asserted) under —
# scenario extra_args override these
ARGS = dict(window_length=500, quality_threshold=10.0, error_threshold=0.3,
            match=5, mismatch=-4, gap=-8, num_threads=1)

# polish scenarios -> (reads, overlaps, target, extra_args)
# edit distance of the revcomp'd single polished contig vs NC_001416
POLISH = {
    "paf": ("sample_reads.fastq.gz", "sample_overlaps.paf.gz",
            "sample_layout.fasta.gz", {}),
    "sam": ("sample_reads.fastq.gz", "sample_overlaps.sam.gz",
            "sample_layout.fasta.gz", {}),
    "sam_noq": ("sample_reads.fasta.gz", "sample_overlaps.sam.gz",
                "sample_layout.fasta.gz", {}),
    "paf_noq": ("sample_reads.fasta.gz", "sample_overlaps.paf.gz",
                "sample_layout.fasta.gz", {}),
    "paf_w1000": ("sample_reads.fastq.gz", "sample_overlaps.paf.gz",
                  "sample_layout.fasta.gz", {"window_length": 1000}),
    "unit": ("sample_reads.fastq.gz", "sample_overlaps.paf.gz",
             "sample_layout.fasta.gz",
             {"match": 1, "mismatch": -1, "gap": -1}),
}

# fragment-correction scenarios -> (reads, overlaps, target, extra_args)
# pinned as (record_count, total_corrected_bases)
FRAGMENT = {
    "kc": ("sample_reads.fastq.gz", "sample_ava_overlaps.paf.gz",
           "sample_reads.fastq.gz",
           {"match": 1, "mismatch": -1, "gap": -1}),
    "kf_fasta": ("sample_reads.fasta.gz", "sample_ava_overlaps.paf.gz",
                 "sample_reads.fasta.gz",
                 {"fragment_correction": True, "match": 1, "mismatch": -1,
                  "gap": -1, "drop": False}),
    "kf_paf": ("sample_reads.fastq.gz", "sample_ava_overlaps.paf.gz",
               "sample_reads.fastq.gz",
               {"fragment_correction": True, "match": 1, "mismatch": -1,
                "gap": -1, "drop": False}),
    "kf_mhap": ("sample_reads.fastq.gz", "sample_ava_overlaps.mhap.gz",
                "sample_reads.fastq.gz",
                {"fragment_correction": True, "match": 1, "mismatch": -1,
                 "gap": -1, "drop": False}),
}

# host path (CPU SPOA-parity engine) — asserted in tests/test_golden.py;
# reference CPU numbers in comments for comparison
HOST_POLISH = {
    "paf": 1283,        # reference: 1312
    "sam": 1315,        # reference: 1317
    "sam_noq": 1769,    # reference: 1770
    "paf_noq": 1443,    # reference: 1566
    "paf_w1000": 1304,  # reference: 1289
    "unit": 1338,       # reference: 1321
}
HOST_FRAGMENT = {
    "kc": (40, 401215),            # reference: 40 / 401246
    "kf_fasta": (236, 1662904),    # reference: 236 / 1663982 (GPU 1663732)
    "kf_paf": (236, 1657837),      # reference: 236 / 1658216
    # identical to kf_paf, as in the reference (its MHAP and PAF kF pins
    # are both 1658216, racon_test.cpp:252-258,288-294): the MHAP ordinal
    # transmutation resolves to the same overlaps bit-for-bit
    "kf_mhap": (236, 1657837),     # reference: 236 / 1658216
}

# device path (fused Pallas kernel on a real TPU chip) — refreshed by
# pin_device_golden.py on a machine with the chip. The reference's GPU
# pins differ from its CPU pins the same way (racon_test.cpp:316-318).
# Pins isolate the consensus device path: phase 1 runs on the HOST aligner
# (pin_device_golden.py pins RACON_TPU_DEVICE_ALIGNER=host; the paf=1282
# measurement predates the hirschberg-on-TPU default and was host-phase-1).
DEVICE_POLISH = {
    "paf": 1282,        # v5e, 2026-07-29: one edit from host's 1283
    "sam": None,
    "sam_noq": None,
    "paf_noq": None,
    "paf_w1000": None,
    "unit": None,
}
DEVICE_FRAGMENT = {
    "kc": None,
    "kf_fasta": None,
    "kf_paf": None,
    "kf_mhap": None,
}
