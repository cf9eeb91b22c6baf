#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the device path still starts
on the chip and still gives the right answer.

Drives the polishing path once, through the entry points a user calls,
at the shapes racon's users run (ONT reads of mean 8 kb at 5/3/3 %
sub/ins/del, 30x, ``-w 500 -m 5 -x -4 -g -8``, default batch and tiers,
no ``RACON_TPU_*`` override).  Scale is genome length: 0.5 Mbp by
default, against the 4.6 Mbp of BASELINE.json config 2 — the cut is
printed under ``reduced``.  Data is generated from ``--seed``; nothing
is downloaded.

Stages, each a child process run to completion before the next starts —
this orchestrator never imports JAX, so the chip has one owner at a time:

  build     libracon_host.so from racon_tpu/native/src, on this machine
  data      racon_tpu.tools.simulate
  paf       ``racon_tpu.cli --tpu`` on the PAF input (alignment +
            consensus on the device), cold, then warm from the cache
  sam       the same on the SAM input (consensus only)
  host      the host oracle on the PAF input
  serve     ``racon_tpu.cli serve --backend tpu``: start-up warm-up, the
            PAF and SAM jobs through ServeClient, byte-identical to the
            one-shot outputs; a host-lane job next to them, identical to
            the oracle (the host path stays off the chip the daemon
            holds); clean shutdown
  verdict   edit distance of draft / host / device contigs to the truth

Every device stage is judged from its run report (``judge_report``):
platform ``tpu``, no degradation, ``xla`` at zero, ``ls`` and
``hirschberg`` serving their stated shares, the warm run loaded from the
persistent cache.  A smoke that passes with ``ls`` at zero is the failure
this script exists to prevent.

Without a TPU it exits non-zero and prints no result.  ``--rehearse`` is
the same flow at toy size on a CPU asked for by name
(``JAX_PLATFORMS=cpu``, interpreted kernels): every line it prints is
labelled a rehearsal and it can never print the pass line.

Last line of stdout on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import filecmp
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

SCORES = ["-m", "5", "-x", "-4", "-g", "-8"]
WINDOW = 500
COVERAGE = 30
MEAN_READ = 8000
FULL_MBP = 4.6                  # BASELINE.json config 2
BUDGET_S = 1150                 # the contract allows 1200, compile included

# -- what a healthy report looks like --------------------------------------
LS_MIN_SHARE = 0.90             # of the windows that reach a kernel
HOST_WINDOW_CEILING = 0.08      # capacity rejections (builders saw 4 of
                                # 96 λ windows at node factor 3 = 4.2 %)
HIRSCHBERG_MIN_SHARE = 0.80     # the rest: band or length ineligible
DEVICE_VS_HOST_MARGIN = 0.10    # device edit distance within 10 % of the
DEVICE_VS_HOST_PER_BP = 2e-4    # host's, or 2 edits per 10 kb if larger
POLISH_MIN_GAIN = 0.5           # ...and <= 0.5 x the draft's


def judge_report(rep: dict, *, alignment: bool, rehearsal: bool = False,
                 warm: bool = False) -> list:
    """Problems with one device-path run report ([] = healthy).

    ``alignment``: the run had a device alignment phase (PAF input).
    ``rehearsal``: CPU platform and interpreted kernels are expected.
    ``warm``: the run must have loaded every kernel from the persistent
    compilation cache."""
    bad = []
    dev = rep.get("device") or {}
    want = "cpu" if rehearsal else "tpu"
    if dev.get("platform") != want:
        bad.append(f"platform {dev.get('platform')!r}, expected {want!r}")
    phases = rep.get("phases") or {}

    cons = phases.get("consensus")
    if not cons:
        bad.append("no consensus phase in the report")
    else:
        served = cons.get("served", {})
        kernel_windows = cons.get("total", 0) - served.get("backbone", 0)
        if cons.get("degradations"):
            bad.append(f"consensus degraded: {cons['degradations']}")
        if served.get("xla", 0):
            bad.append(f"consensus tier xla served {served['xla']}")
        if served.get("ls", 0) < LS_MIN_SHARE * max(kernel_windows, 1):
            bad.append(f"ls served {served.get('ls', 0)} of "
                       f"{kernel_windows} windows (< {LS_MIN_SHARE:.0%})")
        if served.get("host", 0) > HOST_WINDOW_CEILING * max(
                kernel_windows, 1):
            bad.append(f"host served {served.get('host', 0)} of "
                       f"{kernel_windows} windows "
                       f"(> {HOST_WINDOW_CEILING:.0%})")
        bad += _clean_lattice("consensus", cons, rehearsal)

    ali = phases.get("alignment")
    if alignment:
        if not ali or not ali.get("total"):
            bad.append("no alignment jobs in the report")
        else:
            served = ali.get("served", {})
            if ali.get("degradations"):
                bad.append(f"alignment degraded: {ali['degradations']}")
            if served.get("hirschberg", 0) < (HIRSCHBERG_MIN_SHARE
                                              * ali["total"]):
                bad.append(f"hirschberg served {served.get('hirschberg', 0)}"
                           f" of {ali['total']} jobs "
                           f"(< {HIRSCHBERG_MIN_SHARE:.0%})")
            bad += _clean_lattice("alignment", ali, rehearsal)

    counters = ((rep.get("obs") or {}).get("metrics") or {}).get(
        "counters") or {}
    if counters.get("shard.demotions", 0):
        bad.append(f"shard demotions: {counters['shard.demotions']}")
    if dev.get("count", 1) > 1:
        rows = [counters.get(f"shard.rows.d{i}", 0)
                for i in range(dev["count"])]
        if not all(rows):
            bad.append(f"rows not spread over all devices: {rows}")
    if warm:
        cache = rep.get("jax_cache") or {}
        if cache.get("misses", 1) or not cache.get("hits", 0):
            bad.append(f"warm run did not load from the compile cache: "
                       f"{cache}")
    return bad


def _clean_lattice(name: str, phase: dict, rehearsal: bool) -> list:
    bad = []
    for key in ("retries", "bisections"):
        if phase.get(key):
            bad.append(f"{name} {key}: {phase[key]}")
    if phase.get("quarantined"):
        bad.append(f"{name} quarantined: {phase['quarantined']}")
    kernels = (phase.get("extra") or {}).get("kernels") or {}
    if bool(kernels.get("interpreted")) != rehearsal:
        bad.append(f"{name} kernels interpreted="
                   f"{kernels.get('interpreted')}")
    return bad


def report_line(rep: dict) -> dict:
    """What a stage prints from its report: who served what, where."""
    out = {"device": rep.get("device"), "jax_cache": rep.get("jax_cache"),
           "report_wall_s": rep.get("wall_s")}
    for name, ph in (rep.get("phases") or {}).items():
        extra = ph.get("extra") or {}
        out[name] = {"total": ph.get("total"), "served": ph.get("served"),
                     "degradations": len(ph.get("degradations") or []),
                     "wall_s": ph.get("wall_s"),
                     **{k: extra.get(k) for k in (
                         "kernels", "device_rejected", "pack_wall_s",
                         "kernel_wall_s")}}
    metrics = (rep.get("obs") or {}).get("metrics") or {}
    counters = metrics.get("counters") or {}
    out["counters"] = {k: v for k, v in sorted(counters.items())
                       if k.startswith(("kernel.builds", "shard."))}
    build = (metrics.get("histograms") or {}).get("span_us.kernel.build")
    out["kernel_build_s"] = (round(build.get("sum", 0.0) / 1e6, 3)
                             if build else 0.0)
    return out


# -- the orchestrator ------------------------------------------------------

class Smoke:
    def __init__(self, args):
        self.rehearsal = args.rehearse
        self.mbp = args.mbp if args.mbp else (0.01 if self.rehearsal
                                              else 0.5)
        self.seed = args.seed
        self.out = os.path.abspath(args.out)
        self.tag = "[REHEARSAL on cpu, not a chip result] " \
            if self.rehearsal else ""
        self.t0 = time.monotonic()
        self.threads = str(min(os.cpu_count() or 1, 16))
        self.failures = []
        self.summary = {"rehearsal": self.rehearsal, "mbp": self.mbp,
                        "seed": self.seed, "stages": {}, "claim": None}
        self.device = None
        self.data_dir = None
        # children that never touch the device must not take the chip
        self.host_env = dict(os.environ, JAX_PLATFORMS="cpu")
        # On a CPU the drivers pick the XLA twin and the host aligner by
        # themselves; the rehearsal asks for the tiers the chip runs,
        # interpreted.  The chip run sets nothing.
        self.device_env = dict(
            os.environ, RACON_TPU_PALLAS="1",
            RACON_TPU_DEVICE_ALIGNER="hirschberg") if self.rehearsal \
            else None

    def say(self, msg: str) -> None:
        print(f"{self.tag}{msg}", flush=True)

    def fail(self, stage: str, why: str) -> None:
        self.failures.append(f"{stage}: {why}")
        self.say(f"FAIL {stage}: {why}")

    def left(self) -> float:
        return BUDGET_S - (time.monotonic() - self.t0)

    def run(self, stage: str, cmd, *, env=None, stdout=None,
            timeout=None) -> bool:
        """One child, run to completion; its stderr goes to the output
        directory and its tail is shown on failure."""
        err_path = os.path.join(self.out, f"{stage}.stderr")
        timeout = min(timeout or BUDGET_S, max(self.left(), 1.0))
        t = time.monotonic()
        with open(err_path, "w") as err:
            out_f = open(stdout, "wb") if stdout else subprocess.DEVNULL
            try:
                rc = subprocess.run(cmd, cwd=ROOT, env=env, stdout=out_f,
                                    stderr=err, timeout=timeout).returncode
            except subprocess.TimeoutExpired:
                rc = f"timeout after {timeout:.0f}s"
            finally:
                if stdout:
                    out_f.close()
        wall = round(time.monotonic() - t, 2)
        self.summary["stages"].setdefault(stage, {})["wall_s"] = wall
        if rc != 0:
            with open(err_path, errors="replace") as f:
                tail = f.read()[-1500:]
            self.fail(stage, f"exit {rc} after {wall}s\n{tail}")
            return False
        self.say(f"{stage}: ok in {wall}s")
        return True

    # -- stages ------------------------------------------------------------

    def check_device(self) -> bool:
        out = os.path.join(self.out, "device.json")
        if not self.run("device", [sys.executable, "-m",
                                   "racon_tpu.device"], stdout=out,
                        env=self.device_env):
            return False
        with open(out) as f:
            self.device = json.loads(f.read().strip().splitlines()[-1])
        self.summary["device"] = self.device
        want = "cpu" if self.rehearsal else "tpu"
        if self.device["platform"] != want:
            self.fail("device", f"JAX found {self.device}; "
                      + ("--rehearse needs JAX_PLATFORMS=cpu"
                         if self.rehearsal else
                         "no accelerator (--rehearse runs the CPU "
                         "rehearsal)"))
            return False
        self.say(f"device: {self.device}")
        return True

    def build(self) -> bool:
        # the copy on disk may hold a library built with -march=native
        # on another machine; build from the committed sources here
        shutil.rmtree(os.path.join(ROOT, "racon_tpu", "native", "build"),
                      ignore_errors=True)
        return self.run("build", ["make", "-C", "racon_tpu/native", "-j",
                                  str(os.cpu_count() or 4)])

    def data(self) -> bool:
        # not under the output directory: at 0.5 Mbp the reads and
        # overlaps alone outgrow what the chip tool copies back
        self.data_dir = tempfile.mkdtemp(prefix="chip_smoke_data.")
        return self.run("data", [
            sys.executable, "-m", "racon_tpu.tools.simulate",
            "-o", self.data_dir, "--mbp", str(self.mbp),
            "--coverage", str(COVERAGE), "--mean-read", str(MEAN_READ),
            "--seed", str(self.seed)], env=self.host_env)

    def inputs(self, kind: str):
        d = self.data_dir
        return [os.path.join(d, "reads.fastq"),
                os.path.join(d, f"overlaps.{kind}"),
                os.path.join(d, "draft.fasta")]

    def polish(self, kind: str, temp: str):
        """One ``racon_tpu.cli --tpu`` run; returns the output path."""
        stage = f"{kind}_{temp}"
        fasta = os.path.join(self.out, f"{stage}.fasta")
        report = os.path.join(self.out, f"{stage}.report.json")
        if not self.run(stage, [
                sys.executable, "-m", "racon_tpu.cli", "--tpu",
                "-w", str(WINDOW), *SCORES, "-t", self.threads,
                "--report", report,
                "--trace", os.path.join(self.out, f"{stage}.trace.json"),
                *self.inputs(kind)], stdout=fasta, env=self.device_env):
            return None
        self.judge(stage, report, alignment=(kind == "paf"),
                   warm=(temp == "warm"))
        return fasta

    def judge(self, stage: str, report_path: str, *, alignment: bool,
              warm: bool) -> None:
        with open(report_path) as f:
            rep = json.load(f)
        line = report_line(rep)
        self.summary["stages"].setdefault(stage, {}).update(line)
        self.say(f"{stage}: {json.dumps(line, sort_keys=True)}")
        for problem in judge_report(rep, alignment=alignment,
                                    rehearsal=self.rehearsal, warm=warm):
            self.fail(stage, problem)

    def one_shot(self, kind: str):
        cold = self.polish(kind, "cold")
        warm = self.polish(kind, "warm") if cold else None
        if cold and warm and not filecmp.cmp(cold, warm, shallow=False):
            self.fail(f"{kind}_warm", "output differs from the cold run")
        return cold

    def serve(self, jobs: list) -> None:
        """The daemon: warm-up, then ``jobs`` — (input kind, backend, the
        one-shot output it must equal byte for byte) — through
        ServeClient, and a clean shutdown.  The ``cpu`` job runs on the
        host lane, a CLI child of a daemon that holds the chip: it only
        finishes if the host path stays off the device."""
        from racon_tpu.serve.client import ServeClient, ServeError

        state = os.path.join(self.out, "serve")
        shutil.rmtree(state, ignore_errors=True)
        os.makedirs(state)
        t = time.monotonic()
        with open(os.path.join(self.out, "serve.stderr"), "w") as err:
            daemon = subprocess.Popen(
                [sys.executable, "-m", "racon_tpu.cli", "serve",
                 "--backend", "tpu", "--state-dir", state, "--port", "0",
                 "--warm-window", str(WINDOW), *SCORES],
                cwd=ROOT, env=self.device_env,
                stdout=subprocess.DEVNULL, stderr=err)
        try:
            client = self._connect(daemon, state)
            if client is None:
                return
            with client:
                ping = client.ping()
                self.say(f"serve: up in {time.monotonic() - t:.1f}s, "
                         f"device {ping.get('device')}, stats "
                         f"{json.dumps(client.stats().get('session'))}")
                args = {"window_length": WINDOW, "match": 5,
                        "mismatch": -4, "gap": -8,
                        "num_threads": int(self.threads)}
                for kind, backend, oneshot in jobs:
                    stage = f"serve_{kind}_{backend}"
                    t1 = time.monotonic()
                    try:
                        job = client.submit(*self.inputs(kind), args=args,
                                            backend=backend)
                        resp = client.wait(job, timeout=max(self.left(), 1))
                    except (ServeError, OSError) as e:
                        self.fail(stage, f"{type(e).__name__}: {e}")
                        continue
                    res = resp["result"]
                    self.summary["stages"][stage] = {
                        "wall_s": round(time.monotonic() - t1, 2),
                        "lane": resp.get("lane"),
                        "demotions": resp.get("demotions"),
                        "kernel_builds": res.get("kernel_builds")}
                    self.say(f"{stage}: "
                             f"{json.dumps(self.summary['stages'][stage])}")
                    if resp.get("demotions") or res.get("backend") != backend:
                        self.fail(stage, f"job left its lane: "
                                  f"{resp.get('demotions')}")
                    if not filecmp.cmp(res["output"], oneshot,
                                       shallow=False):
                        self.fail(stage, "served output differs from the "
                                  "one-shot CLI output")
                    if backend == "tpu":
                        self.judge(stage, res["report"],
                                   alignment=(kind == "paf"), warm=False)
                client.shutdown()
            try:
                rc = daemon.wait(timeout=60)
                if rc != 0:
                    self.fail("serve", f"daemon exited {rc}")
            except subprocess.TimeoutExpired:
                self.fail("serve", "daemon did not stop on shutdown")
        finally:
            if daemon.poll() is None:
                daemon.kill()
                daemon.wait()
        self.summary["stages"].setdefault("serve", {})["wall_s"] = round(
            time.monotonic() - t, 2)

    def _connect(self, daemon, state):
        from racon_tpu.serve.client import ServeClient

        deadline = time.monotonic() + min(600, max(self.left(), 1))
        while time.monotonic() < deadline:
            if daemon.poll() is not None:
                with open(os.path.join(self.out, "serve.stderr"),
                          errors="replace") as f:
                    self.fail("serve", f"daemon exited {daemon.returncode} "
                              f"at start-up\n{f.read()[-1500:]}")
                return None
            try:
                client = ServeClient.from_state_dir(
                    state, timeout=max(self.left(), 1))
                client.ping()       # answers once the warm-up is done
                return client
            except (OSError, ValueError):
                time.sleep(0.5)
        self.fail("serve", "daemon did not come up")
        return None

    def host_oracle(self):
        fasta = os.path.join(self.out, "host_paf.fasta")
        ok = self.run("host_paf", [
            sys.executable, "-m", "racon_tpu.cli", "-w", str(WINDOW),
            *SCORES, "-t", self.threads, *self.inputs("paf")],
            env=self.host_env, stdout=fasta)
        return fasta if ok else None

    def verdict(self, host: str, device: dict) -> None:
        """Edit distance of draft, host-polished and device-polished
        contigs to the truth genome."""
        out = os.path.join(self.out, "verdict.json")
        names = {"draft": os.path.join(self.data_dir, "draft.fasta"),
                 "host_paf": host,
                 **{f"device_{k}": v for k, v in device.items()}}
        code = (
            "import json, sys\n"
            "from racon_tpu import native\n"
            "def seq(p):\n"
            "    return b''.join(l.strip().encode() for l in open(p)\n"
            "                    if not l.startswith('>'))\n"
            "names = json.loads(sys.argv[1])\n"
            "truth = seq(sys.argv[2])\n"
            "print(json.dumps({k: native.edit_distance(seq(p), truth)\n"
            "                  for k, p in names.items()}))\n")
        if not self.run("verdict", [
                sys.executable, "-c", code, json.dumps(names),
                os.path.join(self.data_dir, "genome.fasta")],
                env=self.host_env, stdout=out):
            return
        with open(out) as f:
            ed = json.loads(f.read().strip().splitlines()[-1])
        self.summary["edit_distance"] = ed
        self.say(f"edit distance to the truth genome: {json.dumps(ed)}")
        for name, d in ed.items():
            if not name.startswith("device_"):
                continue
            slack = max(DEVICE_VS_HOST_MARGIN * ed["host_paf"],
                        DEVICE_VS_HOST_PER_BP * self.mbp * 1e6)
            if d > ed["host_paf"] + slack:
                self.fail("verdict", f"{name} {d} is more than "
                          f"{slack:.0f} above the host's {ed['host_paf']}")
            if d > POLISH_MIN_GAIN * ed["draft"]:
                self.fail("verdict", f"{name} {d} is not far below the "
                          f"draft's {ed['draft']}")

    # -- the whole thing ---------------------------------------------------

    def main(self) -> int:
        os.makedirs(self.out, exist_ok=True)
        self.say(f"chip_smoke: {self.mbp} Mbp, {COVERAGE}x ONT mean "
                 f"{MEAN_READ}, -w {WINDOW} {' '.join(SCORES)}, seed "
                 f"{self.seed}, output {self.out}")
        if self.mbp < FULL_MBP:
            self.summary["reduced"] = {
                "genome_mbp": [FULL_MBP, self.mbp],
                "why": "scale only, to fit the smoke's time limit; read "
                       "length, error mix, depth, window and scores are "
                       "the source's"}
            self.say(f"reduced: genome {self.mbp} Mbp of BASELINE config "
                     f"2's {FULL_MBP} Mbp (scale only)")
        try:
            if self.check_device() and self.build() and self.data():
                paf = self.one_shot("paf")
                sam = self.one_shot("sam")
                host = self.host_oracle()
                done = {k: v for k, v in (("paf", paf), ("sam", sam)) if v}
                jobs = [(k, "tpu", v) for k, v in done.items()]
                if host:
                    jobs.append(("paf", "cpu", host))
                if jobs:
                    self.serve(jobs)
                if host and done:
                    self.verdict(host, done)
                if len(done) < 2:
                    self.fail("smoke", "a one-shot stage produced nothing")
        finally:
            if self.data_dir:
                shutil.rmtree(self.data_dir, ignore_errors=True)
            self.summary["wall_s"] = round(time.monotonic() - self.t0, 1)
            self.summary["failures"] = self.failures
            with open(os.path.join(self.out, "summary.json"), "w") as f:
                json.dump(self.summary, f, indent=1, sort_keys=True)
                f.write("\n")
        self.say("summary: " + json.dumps(self.summary, sort_keys=True))
        if self.failures:
            self.say(f"chip_smoke FAILED ({len(self.failures)} problem(s))")
            return 1
        if self.rehearsal:
            self.say("rehearsal passed; this is not a chip result")
            return 0
        d = self.device
        print(json.dumps({"ok": True, "device": {
            "platform": d["platform"], "kind": d["device_kind"],
            "count": d["count"]}}), flush=True)
        return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--rehearse", action="store_true",
                   help="toy-size CPU rehearsal (needs JAX_PLATFORMS=cpu); "
                        "never prints the pass line")
    p.add_argument("--mbp", type=float, default=0.0,
                   help="genome size (default 0.5; 0.01 with --rehearse)")
    p.add_argument("--seed", type=int, default=11)
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                 "chip_smoke"))
    args = p.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "racon_tpu", "native", "src")):
        print("chip_smoke: the racon_tpu package is not next to this "
              "script; nothing to drive", file=sys.stderr)
        return 2
    return Smoke(args).main()


if __name__ == "__main__":
    sys.exit(main())
