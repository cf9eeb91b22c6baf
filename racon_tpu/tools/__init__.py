"""Workflow tooling around the polisher: sequence subsampling/splitting
(rampler-equivalent), the outer wrapper that chains them with polishing runs,
and paired-end read preprocessing. Capability parity with the reference's
scripts/ + vendored rampler (/root/reference/scripts/racon_wrapper.py,
racon_preprocess.py, vendor/rampler)."""

