"""Multi-device scaling sweep: the MULTICHIP harness's measurement half.

The dryrun gate (``__graft_entry__.dryrun_multichip``) proves the sharded
polish step *works* — compile + run + pallas-vs-XLA-twin byte equality
over an 8-device mesh.  This tool adds the number ROADMAP item 2 actually
asks for: windows/second of the production consensus kernel dispatched
through the partitioner at 1, 2, 4, and 8 mesh shards, so the scaling
curve (near-linear on real chips, flat on forced virtual CPU devices —
they share the same cores) is a committed artifact instead of a claim.

Each device count runs in its OWN bounded subprocess: jax backend init is
one-way, so sweeping mesh widths in-process is impossible.  The sweep
varies ``RACON_TPU_MESH_SHAPE`` (the partitioner under-subscribes the
visible devices), which works identically on a real multi-chip backend
(``--real``) and on the forced virtual-CPU mesh this repo's CI can run.

Output JSON keeps the driver's multichip gate keys (``n_devices``/``rc``/``ok``/
``skipped``/``tail``) and adds ``scaling``: one entry per device count
with the measured windows/s, the shard geometry that served it, and the
worker's ``shard.*`` obs counters (per-device row balance evidence).

Usage:
    python racon_tpu/tools/multichip.py --out MULTICHIP_r06.json
    python racon_tpu/tools/multichip.py --real      # ambient backend
    python racon_tpu/tools/multichip.py --counts 1,2 --skip-gate
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEFAULT_COUNTS = (1, 2, 4, 8)


def _force_cpu_env(base, n_devices):
    """Forced virtual-CPU env for a worker subprocess (same flags the
    dryrun gate forces; loaded from __graft_entry__ by file path so this
    orchestrator never imports jax itself)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_graft_entry_multichip", os.path.join(HERE, "__graft_entry__.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod._force_cpu_env(base, n_devices)


def _worker_env(base, mesh_n, real, force_host):
    env = dict(base)
    if not real:
        env.update(_force_cpu_env(env, force_host))
    env["RACON_TPU_MESH_SHAPE"] = str(mesh_n)
    # one batch geometry across the whole sweep (the CPU default of 4
    # can't even shard 8 ways); 64 divides every count and satisfies the
    # lockstep kernel's G*m grouping at m=8.  An explicit knob wins.
    env.setdefault("RACON_TPU_BATCH_WINDOWS", "64")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (HERE, base.get("PYTHONPATH")) if p)
    return env


def measure(mesh_n: int, repeats: int) -> dict:
    """Worker body: time `repeats` sharded dispatches of the production
    consensus kernel at the ambient mesh width (RACON_TPU_MESH_SHAPE was
    set by the orchestrator before this process initialized jax).

    Tier choice mirrors the driver's reality: the fused 'ls' pallas
    kernel on a TPU backend, its vmapped XLA twin elsewhere (pallas
    interpret mode is minutes/window on CPU — the gate covers it; a
    timing sweep through it would measure the interpreter).  The first
    dispatch is the compile and is timed separately; the measured loop
    blocks on every batch so windows/s includes device round-trips.
    """
    import numpy as np

    sys.path.insert(0, HERE)
    import __graft_entry__ as g
    import jax

    from racon_tpu import obs
    from racon_tpu.ops import poa, poa_driver
    from racon_tpu.parallel.partitioner import get_partitioner

    obs.configure(metrics=True)
    devs = jax.devices()
    tier = "ls" if devs[0].platform == "tpu" else "xla"
    use_pallas = tier != "xla"
    cfg = poa.PoaConfig(max_nodes=256, max_len=128, max_backbone=128,
                        max_edges=8, depth=4, match=5, mismatch=-4, gap=-8)
    B = poa_driver._device_batch(use_pallas)
    args = g._example_batch(cfg, B, np.random.default_rng(0))
    part = get_partitioner()
    shards = part.batch_axis_size if part.will_shard(B) else 1

    t0 = time.monotonic()
    kern = poa_driver._build_kernel(cfg, B, use_pallas)
    res = poa_driver._unpack(poa_driver._submit(kern, args, use_pallas),
                             use_pallas)
    compile_s = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(repeats):
        if shards > 1:
            # same per-dispatch accounting the executor's pad seam emits
            # (B real rows, no padding at this geometry): the committed
            # artifact carries the per-device balance counters
            from racon_tpu.ops.batch_exec import count_shard_rows
            count_shard_rows(B, B, shards)
        res = poa_driver._unpack(
            poa_driver._submit(kern, args, use_pallas), use_pallas)
    wall = time.monotonic() - t0
    assert not res[3].any(), "sweep windows failed on the device kernel"
    snap = obs.snapshot() or {}
    counters = {k: v for k, v in (snap.get("counters") or {}).items()
                if k.startswith("shard.")}
    return {
        "mesh": mesh_n,
        "devices_visible": len(devs),
        "platform": devs[0].platform,
        "tier": tier,
        "batch": B,
        "shards": shards,
        "rows_per_device": B // max(1, shards),
        "repeats": repeats,
        "compile_s": round(compile_s, 3),
        "wall_s": round(wall, 4),
        "windows_per_s": round(B * repeats / wall, 2) if wall > 0 else None,
        "counters": counters,
    }


def _strip_progress(text):
    """Collapse ``\\r``-overwritten progress-bar frames to their final
    state (keep only what follows the last carriage return on each
    line), so the bounded tail captures spend their byte budget on real
    output instead of a hundred redraws of the same bar."""
    return "\n".join(ln.rsplit("\r", 1)[-1]
                     for ln in (text or "").split("\n"))


def _run_worker(mesh_n, repeats, real, force_host, bound_s):
    """One bounded subprocess per device count (backend init is one-way)."""
    cmd = [sys.executable, os.path.abspath(__file__),
           "--worker", str(mesh_n), "--repeats", str(repeats)]
    try:
        r = subprocess.run(
            cmd, cwd=HERE, capture_output=True, text=True, timeout=bound_s,
            env=_worker_env(os.environ, mesh_n, real, force_host))
    except subprocess.TimeoutExpired:
        return {"mesh": mesh_n, "ok": False,
                "error": f"timeout after {bound_s}s"}
    for line in reversed((r.stdout or "").splitlines()):
        if line.startswith("{"):
            try:
                return dict(json.loads(line), ok=r.returncode == 0)
            except ValueError:
                break
    return {"mesh": mesh_n, "ok": False,
            "error": f"rc={r.returncode}",
            "tail": _strip_progress((r.stderr or "")
                                    + (r.stdout or ""))[-800:]}


def sweep(counts=DEFAULT_COUNTS, repeats=3, real=False, force_host=None,
          bound_s=900):
    """Measure windows/s at each device count; returns {count: entry}."""
    force_host = max(counts) if force_host is None else force_host
    out = {}
    for n in counts:
        print(f"[multichip] sweep: {n} device(s)...", file=sys.stderr,
              flush=True)
        out[str(n)] = _run_worker(n, repeats, real, force_host, bound_s)
    return out


def gate(n_devices=8, bound_s=1800):
    """The r05-format dryrun gate: sharded polish step compiles, runs,
    and matches the XLA twin byte-for-byte (plus the 2-process distrib
    fleet rung), in a bounded subprocess."""
    env = dict(os.environ,
               PYTHONPATH=os.pathsep.join(
                   p for p in (HERE, os.environ.get("PYTHONPATH")) if p))
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             f"import __graft_entry__ as g; g.dryrun_multichip({n_devices})"],
            cwd=HERE, capture_output=True, text=True, timeout=bound_s,
            env=env)
        rc, tail = r.returncode, _strip_progress(
            (r.stderr or "") + (r.stdout or ""))[-2000:]
    except subprocess.TimeoutExpired:
        rc, tail = -1, f"gate timeout after {bound_s}s"
    return {"n_devices": n_devices, "rc": rc, "ok": rc == 0,
            "skipped": False, "tail": tail}


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="multichip.py",
        description="1/2/4/8-device scaling sweep + sharded dryrun gate")
    p.add_argument("--counts", default=",".join(map(str, DEFAULT_COUNTS)),
                   help="device counts to sweep (default 1,2,4,8)")
    p.add_argument("--repeats", type=int, default=3,
                   help="timed dispatches per count (default 3)")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="write the harness JSON here (default stdout only)")
    p.add_argument("--real", action="store_true",
                   help="use the ambient backend (silicon); default forces "
                        "a virtual-CPU mesh")
    p.add_argument("--force-host", type=int, default=None, metavar="N",
                   help="virtual host device count to force (default: "
                        "max of --counts; ignored with --real)")
    p.add_argument("--timeout", type=int, default=900, metavar="S",
                   help="bound per sweep subprocess (default 900)")
    p.add_argument("--gate-devices", type=int, default=8, metavar="N")
    p.add_argument("--skip-gate", action="store_true",
                   help="sweep only; skip the byte-identity dryrun gate")
    p.add_argument("--worker", type=int, default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.worker is not None:
        print(json.dumps(measure(args.worker, max(1, args.repeats))))
        return 0

    counts = sorted({int(c) for c in args.counts.split(",") if c.strip()})
    doc = gate(args.gate_devices) if not args.skip_gate else \
        {"n_devices": args.gate_devices, "rc": None, "ok": True,
         "skipped": True, "tail": "gate skipped (--skip-gate)"}
    doc["scaling"] = sweep(counts, repeats=args.repeats, real=args.real,
                           force_host=args.force_host,
                           bound_s=args.timeout)
    doc["forced"] = not args.real
    doc["ok"] = bool(doc["ok"]) and all(
        e.get("ok") for e in doc["scaling"].values())
    blob = json.dumps(doc, indent=2, sort_keys=True) + "\n"
    if args.out:
        path = args.out if os.path.isabs(args.out) \
            else os.path.join(HERE, args.out)
        with open(path, "w") as f:
            f.write(blob)
        print(f"[multichip] wrote {path}", file=sys.stderr)
    print(blob, end="")
    return 0 if doc["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
