#!/bin/sh
# Repo check runner: first-party static analysis + generic lint + types +
# native hygiene.  Degrades gracefully: third-party tools that are not
# installed are reported and skipped (the container bakes a fixed
# toolchain; nothing is pip-installed on the fly), so the exit code
# reflects only checks that actually ran.
#
# Usage: tools/check.sh [--fast]
#   --fast   skip the jaxpr audit and the native -Werror gate
set -u

root=$(cd "$(dirname "$0")/.." && pwd)
cd "$root" || exit 1
fast=${1:-}

fail=0
run() {  # run <name> <cmd...>
    name=$1; shift
    echo "== $name"
    if "$@"; then
        echo "   ok"
    else
        echo "   FAIL: $name"
        fail=1
    fi
}

skip() {
    echo "== $1"
    echo "   skipped: $2"
}

# 1. First-party analyzer: repo-specific TPU invariants + jaxpr audit.
if [ "$fast" = "--fast" ]; then
    run "racon_tpu.analysis (lint only)" \
        env JAX_PLATFORMS=cpu python -m racon_tpu.analysis --no-jaxpr
else
    run "racon_tpu.analysis" \
        env JAX_PLATFORMS=cpu python -m racon_tpu.analysis
fi

# 1b. Focused lint over the preemption-tolerance modules: these carry
#     the crash-resume contract (journal/watchdog) and the
#     drivers that feed the journal, so their fault points / knob docs /
#     broad-except waivers must stay lint-clean even when a full-tree
#     run is baselined.
run "racon_tpu.analysis (resilience focus)" \
    env JAX_PLATFORMS=cpu python -m racon_tpu.analysis --paths \
        racon_tpu/resilience/journal.py \
        racon_tpu/resilience/watchdog.py \
        racon_tpu/resilience/faults.py \
        racon_tpu/resilience/lattice.py \
        racon_tpu/ops/poa_driver.py \
        racon_tpu/ops/align_driver.py \
        racon_tpu/polisher.py

# 1c. Focused lint over the observability layer: the tracer must stay on
#     the monotonic clock (wall-clock rule scopes racon_tpu/obs/), its
#     knobs must stay documented, and the instrumented seams
#     (kernel_cache, report) must keep their invariants.
run "racon_tpu.analysis (obs focus)" \
    env JAX_PLATFORMS=cpu python -m racon_tpu.analysis --paths \
        racon_tpu/obs/__init__.py \
        racon_tpu/obs/tracer.py \
        racon_tpu/obs/metrics.py \
        racon_tpu/obs/__main__.py \
        racon_tpu/ops/kernel_cache.py \
        racon_tpu/resilience/report.py

# 1d. Concurrency & contract audits: lock discipline over inferred
#     thread roles, lock-order acyclicity, lattice/fault-point drill
#     coverage, wire-protocol field agreement.  (A full-tree run in 1
#     already includes these; this focused invocation keeps them green
#     even under --fast / a baselined full run.)
run "racon_tpu.analysis (concurrency + contracts)" \
    env JAX_PLATFORMS=cpu python -m racon_tpu.analysis \
        --concurrency --contracts

# 1e. Determinism taint audit: the byte-identity contract (no
#     cost-only knob value may reach the consensus/CIGAR install
#     seams; every complete fingerprint composition covers the
#     output-affecting domain), plus the seeded-mutant self-test —
#     each planted contract bug must be CAUGHT (non-zero exit).
run "racon_tpu.analysis (determinism)" \
    env JAX_PLATFORMS=cpu python -m racon_tpu.analysis --determinism
det_mutants() {
    for m in drop-input-bytes leak-pipeline-depth overkey-tier \
             drop-journal-waiver; do
        if env JAX_PLATFORMS=cpu python -m racon_tpu.analysis \
            --det-mutate "$m" > /dev/null; then
            echo "   determinism mutant $m: MISSED"
            return 1
        fi
    done
    return 0
}
run "racon_tpu.analysis (determinism mutants)" det_mutants

# 2. ruff (style + pyflakes), configured in pyproject.toml.
if command -v ruff >/dev/null 2>&1; then
    run "ruff" ruff check .
else
    skip "ruff" "not installed"
fi

# 3. mypy (type drift in the pure-Python drivers).
if command -v mypy >/dev/null 2>&1; then
    run "mypy" mypy
else
    skip "mypy" "not installed"
fi

# 4. Native hygiene: -Wall -Wextra -Werror syntax gate (+clang-tidy when
#    available; the Makefile handles that probe itself).
if [ "$fast" = "--fast" ]; then
    skip "native lint" "--fast"
else
    run "native lint" make -C racon_tpu/native lint
fi

# 5. Sanitizer matrix: instrumented native builds + the rt_stress race
#    harness under TSan/ASan/UBSan.  Each mode is probed by compiling a
#    trivial program first — a toolchain without that sanitizer runtime
#    (common on minimal images) skips with a notice instead of failing.
san_probe() {  # san_probe <flag>  -> 0 when the toolchain supports it
    probe_dir=$(mktemp -d) || return 1
    printf 'int main(void){return 0;}\n' > "$probe_dir/probe.c"
    ${CXX:-g++} "$1" "$probe_dir/probe.c" -o "$probe_dir/probe" \
        >/dev/null 2>&1
    rc=$?
    rm -rf "$probe_dir"
    return $rc
}

if [ "$fast" = "--fast" ]; then
    skip "sanitizers (asan/tsan/ubsan)" "--fast"
else
    for mode in asan tsan ubsan; do
        case $mode in
            asan)  flag=-fsanitize=address ;;
            tsan)  flag=-fsanitize=thread ;;
            ubsan) flag=-fsanitize=undefined ;;
        esac
        if san_probe "$flag"; then
            run "native $mode (rt_test + rt_stress)" \
                make -C racon_tpu/native "$mode"
        else
            skip "native $mode" "toolchain lacks $flag"
        fi
    done
fi

exit $fail
