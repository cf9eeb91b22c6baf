"""Set-up that needs no device: the native library built on this machine,
the cell's data made from the seed, the host oracle's output.

All three are cached under ``benchmark/.cache/`` by what they depend on,
so a cell's later runs in a checkout reuse them; the cache is at a fixed
path inside the checkout and is git-ignored.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import time

from . import generate
from .loader import BENCH_DIR, ROOT, BenchmarkError

CACHE = os.path.join(BENCH_DIR, ".cache")
NATIVE = os.path.join(ROOT, "racon_tpu", "native")


def _sha(*parts) -> str:
    h = hashlib.sha256()
    for p in parts:
        h.update(p if isinstance(p, bytes) else str(p).encode())
        h.update(b"\0")
    return h.hexdigest()[:16]


def _tree_hash(directory: str, extra=()) -> str:
    h = hashlib.sha256()
    files = [os.path.join(directory, f) for f in sorted(os.listdir(directory))]
    for path in files + list(extra):
        if os.path.isfile(path):
            h.update(os.path.basename(path).encode() + b"\0")
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:16]


def native_src_hash() -> str:
    return _tree_hash(os.path.join(NATIVE, "src"),
                      [os.path.join(NATIVE, "Makefile")])


def _cpu_id() -> str:
    """Model and feature flags of this machine's CPU: the library is
    built ``-march=native``, so one built elsewhere may not run here."""
    model = flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("model name") and not model:
                    model = line.split(":", 1)[1].strip()
                elif line.startswith("flags") and not flags:
                    flags = line.split(":", 1)[1].strip()
                if model and flags:
                    break
    except OSError:
        pass
    return f"{model}|{_sha(flags)}"


def ensure_native() -> dict:
    """Build ``libracon_host.so`` from source unless the stamp says the
    one on disk was built here from these sources.  The chip tool copies
    ``racon_tpu/native/build/`` from another CPU; the stamp catches it."""
    stamp_path = os.path.join(CACHE, "native.stamp.json")
    lib = os.path.join(NATIVE, "build", "libracon_host.so")
    want = {"cpu": _cpu_id(), "src": native_src_hash()}
    try:
        with open(stamp_path) as f:
            have = json.load(f)
    except (OSError, json.JSONDecodeError):
        have = None
    if have == want and os.path.exists(lib):
        return {"rebuilt": False, **want}
    t0 = time.monotonic()
    shutil.rmtree(os.path.join(NATIVE, "build"), ignore_errors=True)
    proc = subprocess.run(["make", "-C", NATIVE, "-j",
                           str(os.cpu_count() or 4)],
                          capture_output=True, text=True)
    if proc.returncode != 0:
        raise BenchmarkError("native build failed:\n"
                             + proc.stdout[-2000:] + proc.stderr[-2000:])
    os.makedirs(CACHE, exist_ok=True)
    with open(stamp_path, "w") as f:
        json.dump(want, f)
    return {"rebuilt": True, "build_s": time.monotonic() - t0, **want}


def data_params(cell, rehearsal: bool) -> dict:
    """Generator parameters of a cell: the configuration's read profile,
    then the traffic mix's own keys (size, overlap format), then, in the
    CPU rehearsal only, the traffic file's toy-size overrides."""
    params = dict(cell.config["reads"])
    params.update(cell.traffic["data"])
    if rehearsal:
        params.update(cell.traffic.get("rehearsal", {}))
    return params


def ensure_data(cell, seed: int, rehearsal: bool) -> tuple:
    """(directory, facts) of the cell's data for this seed, generated on
    a miss.  ``facts.json`` is written last and marks a complete set."""
    params = data_params(cell, rehearsal)
    key = _sha(generate.source_hash(), json.dumps(params, sort_keys=True),
               seed)
    d = os.path.join(CACHE, "data", f"{cell.traffic_name}.s{seed}.{key}")
    facts_path = os.path.join(d, "facts.json")
    if os.path.exists(facts_path):
        with open(facts_path) as f:
            return d, {**json.load(f), "cached": True}
    shutil.rmtree(d, ignore_errors=True)
    t0 = time.monotonic()
    mode = generate.resolve(params["generator"])
    facts = mode(d, seed, **{k: v for k, v in params.items()
                             if k != "generator"})
    facts.update(params=params, seed=seed,
                 pair_bases=_pair_bases(d, params))
    facts["generate_s"] = time.monotonic() - t0
    with open(facts_path, "w") as f:
        json.dump(facts, f, indent=1)
    return d, {**facts, "cached": False}


def _pair_bases(d: str, params: dict) -> int:
    """Query length + target span over the PAF overlaps: the bytes an
    aligner must at least read (``costs.align_ops_bytes``)."""
    path = os.path.join(d, "overlaps.paf")
    if "paf" not in params.get("formats", ()) or not os.path.exists(path):
        return 0
    total = 0
    with open(path) as f:
        for line in f:
            c = line.split("\t")
            total += int(c[1]) + int(c[8]) - int(c[7])
    return total


def inputs(d: str, params: dict) -> tuple:
    return (os.path.join(d, "reads.fastq"),
            os.path.join(d, "overlaps." + params["overlaps"]),
            os.path.join(d, "draft.fasta"))


def read_fasta(path: str) -> bytes:
    with open(path) as f:
        return b"".join(line.strip().encode() for line in f
                        if not line.startswith(">"))


def ensure_oracle(d: str, params: dict, polish_args: dict,
                  timed: bool) -> tuple:
    """(path of the host path's polished FASTA, wall seconds or None).
    The host path is the plain reference: ``create_polisher`` with
    ``backend="cpu"``, same inputs, same arguments, same threads.  Cached
    beside the data with the native sources' hash in the key; ``timed``
    runs it anew so that the traced run can report its rate."""
    key = _sha(native_src_hash(), json.dumps(polish_args, sort_keys=True),
               params["overlaps"])
    out = os.path.join(d, f"oracle.{key}.fasta")
    if os.path.exists(out) and not timed:
        return out, None
    from racon_tpu import create_polisher

    t0 = time.monotonic()
    polisher = create_polisher(*inputs(d, params), backend="cpu",
                               **polish_args)
    polisher.initialize()
    records = polisher.polish(True)
    wall = time.monotonic() - t0
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        for name, data in records:
            f.write(f">{name}\n{data}\n")
    os.replace(tmp, out)
    return out, wall
