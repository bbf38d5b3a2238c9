#!/usr/bin/env bash
# Lint gate: flake8 (settings in .flake8, max-line-length 120) over the
# production tree — vitax/ (including the vitax/telemetry/ observability
# subsystem), tests/, tools/ (including tools/metrics_report.py) and
# chip_smoke.py — plus the vitax.analysis source lint and a fast subset of the
# compiled-program invariant checks. tests/test_lint.py runs flake8 as a
# tier-1 guard when flake8 is installed; CI images without flake8 get a
# clean skip here too.
set -u
cd "$(dirname "$0")/.."

# these subsystems and their tools must exist and stay inside the linted
# tree (a rename that drops them out of coverage should fail loudly)
for path in vitax/telemetry tools/metrics_report.py \
            vitax/serve tools/serve_bench.py tests/test_serve.py \
            vitax/serve/fleet tests/test_fleet.py \
            vitax/analysis tools/check_invariants.py tests/test_analysis.py \
            vitax/faults.py vitax/supervise.py tools/supervise.py \
            tests/test_faults.py \
            vitax/data/stream tools/make_shards.py tests/test_stream.py \
            vitax/train/control.py tests/test_control.py \
            vitax/checkpoint/snapshot.py vitax/checkpoint/peer.py \
            tests/test_snapshot.py \
            vitax/analysis/concurrency.py vitax/telemetry/threads.py \
            tests/test_concurrency_lint.py \
            vitax/serve/fleet/breaker.py tests/test_chaos.py \
            vitax/serve/quant.py tests/test_quant.py \
            vitax/ops/fused_optimizer.py tests/test_fused_optimizer.py \
            vitax/ops/dequant_matmul.py tests/test_dequant_matmul.py \
            vitax/serve/fleet/autoscale.py vitax/serve/fleet/placement.py \
            vitax/serve/fleet/agent.py vitax/serve/fleet/cache.py \
            tests/test_cache.py tests/test_autoscale.py \
            vitax/arbiter vitax/arbiter/ledger.py vitax/arbiter/policy.py \
            vitax/arbiter/daemon.py tests/test_arbiter.py \
            vitax/programs vitax/programs/registry.py \
            vitax/programs/builder.py vitax/programs/workloads.py \
            vitax/parallel/rules.py tests/test_programs.py \
            tests/test_assembly.py; do
    if [ ! -e "$path" ]; then
        echo "lint: expected $path to exist (lint/test coverage guard)" >&2
        exit 1
    fi
done

# AST lint: stdlib-only, always runs (VTX1xx source findings). tools/ is
# in scope too: VTX109 (network calls without timeout=) guards the load
# generator and report CLIs as much as the serving tree.
python -m vitax.analysis.ast_lint vitax tools || exit 1

# concurrency lint: per-class thread model + VTX200-series rules over the
# threaded runtime AND its tools. VITAX_LINT_SKIP_CONCURRENCY=1 is the
# escape hatch while triaging a new finding.
if [ "${VITAX_LINT_SKIP_CONCURRENCY:-0}" != "1" ]; then
    python -m vitax.analysis.concurrency vitax tools || exit 1
fi

# compiled-program invariants, fast arm subset (VTX-Rnnn; rules.FAST_ARMS —
# one train arm exercising R001-R005, the fused-optimizer arm for R008,
# the scenario arms (probe/distill) for R010, plus the serve arms:
# full-precision, int8, fp8 (R006/R007) and the forced-fused act-quant arm
# for R009.
# VITAX_LINT_SKIP_INVARIANTS=1 skips on boxes without the jax toolchain.
if [ "${VITAX_LINT_SKIP_INVARIANTS:-0}" != "1" ]; then
    python tools/check_invariants.py \
        --arms zero3_overlap fused probe distill serve serve_quant \
               serve_fp8 serve_actquant || exit 1
fi

if ! python -m flake8 --version >/dev/null 2>&1; then
    echo "lint: flake8 not installed; skipping (pip install flake8 to enable)"
    exit 0
fi

exec python -m flake8 vitax/ tests/ tools/ chip_smoke.py
