#!/usr/bin/env bash
# Tier-1 regression gate (ISSUE 1 satellite): runs the ROADMAP.md tier-1
# command and fails if DOTS_PASSED drops below the seed baseline, so test
# regressions are caught mechanically instead of by eyeballing pytest output.
#
# Usage: scripts/check_tier1.sh [BASELINE] [--chaos] [--load]  (default baseline: 600)
#
#   --chaos   also run the fast chaos smoke stage (3-failpoint subset of
#             scripts/chaos_sweep.py) after the test gate (ISSUE 2 satellite)
#             AND the load-sweep smoke gate (small burst + one poison job +
#             one deadline job through the real service; ISSUE 4 satellite)
#   --load    run only the load-sweep smoke gate after the test gate
#
# Always runs the smlint stage first (ISSUE 9): the static-analysis rule
# set (docs/ANALYSIS.md) over the tree plus its --self-check (baseline
# minimality + every rule's firing fixture).  Then the failpoint registry
# gate: registered names must be unique (duplicate registration raises at
# import), documented in docs/RECOVERY.md, and covered by a chaos scenario.  Then the isocalc
# parallel smoke gate (scripts/isocalc_smoke.py): a 2-worker spheroid run
# must produce byte-identical cache shards vs the serial run.  Then the
# trace smoke gate (scripts/trace_smoke.py): a traced spheroid job through
# the real service must emit a schema-valid, Perfetto-loadable trace that
# trace_report.py renders.  Then the perf-sentinel self-check
# (scripts/perf_sentinel.py): the synthetic fixture history under
# tests/data/perf_history must pass against itself and a synthetic
# regression must trip the gate.
#
# Everything here runs on the CPU platform (jax/jaxlib 0.9.0); the chip
# check is chip_smoke.py, which only passes on a TPU — its legs at 16x16 px
# and its must-fail-off-chip contract are tests/test_chip_smoke.py.
#
# Exit codes: 0 = all gates pass, 1 = regression / gate failure.
# The GATE is the dots count, not pytest's rc: the suite was all green at
# PR 21 (BASELINE below) except timing-sensitive tests that can flake on
# a loaded host (tests/test_load_sweep.py).
set -u -o pipefail

BASELINE="600"
RUN_CHAOS=0
RUN_LOAD=0
for arg in "$@"; do
    case "$arg" in
        --chaos) RUN_CHAOS=1; RUN_LOAD=1 ;;
        --load) RUN_LOAD=1 ;;
        *) BASELINE="$arg" ;;
    esac
done
REPO_ROOT="$(cd "$(dirname "$0")/.." && pwd)"
LOG="$(mktemp /tmp/check_tier1.XXXXXX.log)"
trap 'rm -f "$LOG"' EXIT

cd "$REPO_ROOT"

# smlint stage (ISSUE 9, always on): project-invariant static analysis —
# fence-gated write seams, failpoint registry, metric conventions, config
# drift, guarded-by locking, exception hygiene — must report zero NEW
# findings, and --self-check proves the committed suppression baseline is
# minimal and every rule's firing fixture still fires
if ! env JAX_PLATFORMS=cpu python scripts/smlint.py; then
    echo "check_tier1: FAIL — smlint found new findings" >&2
    exit 1
fi
if ! env JAX_PLATFORMS=cpu python scripts/smlint.py --self-check; then
    echo "check_tier1: FAIL — smlint self-check failed" >&2
    exit 1
fi

# analysis drift sentinel (ISSUE 12): the smlint --json totals (per-rule
# finding counts + the static compile-surface census) are band-checked
# against the committed ANALYSIS_r*.json history, so a quietly growing
# suppressed count or compile surface diffs across rounds like a perf
# regression would
SMLINT_JSON="$(mktemp /tmp/smlint_fresh.XXXXXX.json)"
trap 'rm -f "$LOG" "$SMLINT_JSON"' EXIT
if ! env JAX_PLATFORMS=cpu python scripts/smlint.py --json > "$SMLINT_JSON"; then
    echo "check_tier1: FAIL — smlint --json artifact generation failed" >&2
    exit 1
fi
if ! env JAX_PLATFORMS=cpu python scripts/perf_sentinel.py \
        --history "$REPO_ROOT/ANALYSIS_r*.json" --fresh "$SMLINT_JSON" \
        --min-history 1; then
    echo "check_tier1: FAIL — analysis drift sentinel tripped" >&2
    exit 1
fi

# ULP-contract numerics sentinel (ISSUE 15): score the off-lattice
# spheroid fixture on the lattice-bucketed jax backend AND the numpy
# oracle — FDR-rank identity is a HARD gate, per-MSM-component max-ULP
# drift must stay inside the declared COMPONENT_CONTRACTS ceilings, and
# the drift is band-checked against the committed NUMERICS_r*.json
# history (rising drift regresses).
if ! env JAX_PLATFORMS=cpu python scripts/ulp_sentinel.py; then
    echo "check_tier1: FAIL — ULP-contract numerics sentinel tripped" >&2
    exit 1
fi
if ! env JAX_PLATFORMS=cpu python scripts/ulp_sentinel.py --self-check; then
    echo "check_tier1: FAIL — ulp_sentinel self-check failed" >&2
    exit 1
fi

# roofline probe gate (ISSUE 18): the tiny bench shape timed against the
# cost-model floor on this host's measured peaks.  The --min-frac band is
# deliberately loose on CPU (tiny shapes are dispatch-dominated) — the
# gate catches catastrophic regressions (an order of magnitude off the
# model), and proves the probe + cost model stay runnable end to end on
# every CI run
if ! env JAX_PLATFORMS=cpu python scripts/roofline_probe.py --tiny \
        --min-frac 0.00002; then
    echo "check_tier1: FAIL — roofline probe gate failed" >&2
    exit 1
fi

# compile census gate (ISSUE 12): the spheroid fixture through the real
# service on the jax backend — every XLA compilation attributed to a
# COMPILE_SURFACE-registered call site, the signature set closed under a
# second identical-shape job, the sharded path attributed the same way,
# and sm_compile_* live on /metrics with a `compile` trace event
if ! env JAX_PLATFORMS=cpu python scripts/compile_census.py; then
    echo "check_tier1: FAIL — compile census gate failed" >&2
    exit 1
fi

# failpoint registry gate (now DELEGATES to the smlint failpoint-registry
# rule + the runtime scenario-table cross-check the static rule can't see)
if ! env JAX_PLATFORMS=cpu python scripts/chaos_sweep.py --check-docs; then
    echo "check_tier1: FAIL — failpoint registry check failed" >&2
    exit 1
fi

# isocalc parallel smoke gate (ISSUE 3): 2-worker generation on the spheroid
# fixture must merge to byte-identical cache shards vs the serial run
if ! env JAX_PLATFORMS=cpu python scripts/isocalc_smoke.py; then
    echo "check_tier1: FAIL — isocalc parallel smoke gate failed" >&2
    exit 1
fi

# trace smoke gate (ISSUE 5): the spheroid fixture through the real
# in-process service with tracing on must yield a schema-valid,
# Perfetto-loadable trace that scripts/trace_report.py renders.  Then the
# multichip smoke (ISSUE 7) below proves the device-pool scale-out shape.
if ! env JAX_PLATFORMS=cpu python scripts/trace_smoke.py; then
    echo "check_tier1: FAIL — trace smoke gate failed" >&2
    exit 1
fi

# multichip smoke gate (ISSUE 7): a devices=8 submit through the real
# scheduler must claim the whole simulated pool, score through the
# pjit-sharded sub-mesh path, and match the numpy oracle; two 1-chip jobs
# must hold DISTINCT chips concurrently (no single-token serialization)
if ! env JAX_PLATFORMS=cpu python scripts/multichip_smoke.py; then
    echo "check_tier1: FAIL — multichip smoke gate failed" >&2
    exit 1
fi

# device-fault survival gate (ISSUE 14): a 4-chip sharded job on the
# virtual mesh survives a sticky chip death mid-job — the chip is
# probe-attributed and quarantined, the retry resumes from checkpoint on
# the 3 surviving chips with BIT-IDENTICAL stored annotations, the
# quarantine is visible on /debug/devices + /metrics, no later lease
# includes the fenced chip, and a passing re-probe readmits it
if ! env JAX_PLATFORMS=cpu python scripts/device_chaos.py --smoke; then
    echo "check_tier1: FAIL — device-fault survival gate failed" >&2
    exit 1
fi

# cold-start smoke gate (ISSUE 13): a cleared-persistent-cache 64x64
# submit through the real service must deliver its first FDR-rankable
# annotations in < 5 s (proven via /slo attainment), with the trace
# pinning the compile/queue/compute split + first_annotation ordering,
# the streamed `partial` results field populated, and the recorded
# shape-bucket lattice primeable in one pass
if ! env JAX_PLATFORMS=cpu python scripts/coldstart_smoke.py; then
    echo "check_tier1: FAIL — cold-start smoke gate failed" >&2
    exit 1
fi

# resource-exhaustion smoke gate (ISSUE 10): the spheroid fixture through
# the real service under a 64 MB disk budget — trace-drop degrade visible
# on /metrics with golden results, 507 shed at the submit floor, recovery
# after free-up, retention GC keeps done/ under its cap, and the preflight
# fast path stays microseconds-cheap
if ! env JAX_PLATFORMS=cpu python scripts/resource_smoke.py; then
    echo "check_tier1: FAIL — resource-exhaustion smoke gate failed" >&2
    exit 1
fi

# read-path smoke gate (ISSUE 16): the spheroid fixture annotated through
# the real service, then read back over HTTP — cold query answers from the
# columnar segment, the warm repeat is a cache hit with p50 < 50 ms, the
# result matches a brute-force parquet scan, tile bytes are bit-identical
# to a direct engine/png.py render, and /slo carries the read SLI
if ! env JAX_PLATFORMS=cpu python scripts/read_smoke.py; then
    echo "check_tier1: FAIL — read-path smoke gate failed" >&2
    exit 1
fi

# host-loss survival gate (ISSUE 17): a 2-host simulated pod (self +
# one real child process as host h1) loses the whole child host SIGKILL
# mid-sharded-job — the host watchdog evicts its chip range in one unit,
# the in-flight job resumes from checkpoint on the surviving host with
# BIT-IDENTICAL stored annotations, /peers + sm_pod_* metrics show the
# eviction, and the returning host is readmitted half-open immediately
if ! env JAX_PLATFORMS=cpu python scripts/host_chaos.py --smoke; then
    echo "check_tier1: FAIL — host-loss survival gate failed" >&2
    exit 1
fi

# replica failover smoke gate (ISSUE 8): 3 real scheduler replica
# processes over one partitioned spool; killing one mid-score (and pausing
# one into a fence race) must converge every job exactly-once to the
# golden report, with survivors' sm_replica_* metrics proving the takeover
if ! env JAX_PLATFORMS=cpu python scripts/replica_chaos.py --smoke; then
    echo "check_tier1: FAIL — replica failover smoke gate failed" >&2
    exit 1
fi

# live-acquisition failover gate (ISSUE 19): two replicas over one shared
# spool + work dir; SIGKILL and controller drain of the claim-owning
# replica mid-acquisition must both hand the live stream job to the peer,
# which resumes from the chunk-log checkpoint and converges BIT-IDENTICAL
# (check_exact) to the one-shot batch report — exactly-once spool census,
# exactly-once chunk ingest, zero debris
if ! env JAX_PLATFORMS=cpu python scripts/stream_chaos.py --smoke; then
    echo "check_tier1: FAIL — live-acquisition failover gate failed" >&2
    exit 1
fi

# fleet observability gate (ISSUE 20): a 3-replica fleet over one shared
# work dir, one replica SIGKILLed mid-scrape — /fleet/slo must stay a 200
# partial view with per-replica scrape-error evidence (never a 500), and
# once the victim goes stale the merged SLO must be BIT-EQUAL to a
# recomputation from the union of the survivors' raw histogram buckets.
# Then an on-demand /debug/profile capture during a running job must list
# that job's lease hold and map the sm: annotations of its spans to within
# 1 ms of the job-trace records through the sm_clock events (a CPU capture
# holds no device plane: device time is the chip's to show); finally
# the committed PROFILE_r*.json must carry the measured-roofline pins and a
# degraded replay must trip both perf_sentinel bands.
if ! env JAX_PLATFORMS=cpu python scripts/fleet_smoke.py; then
    echo "check_tier1: FAIL — fleet observability gate failed" >&2
    exit 1
fi

# elastic-fleet smoke gate (ISSUE 11): a lock-order-instrumented
# FleetController over bare replica subprocesses must scale 1→4 under a
# traffic surge and drain back to 2 under cooldown, with every job done/
# exactly once, bounded p99 queue-wait, zero orphaned leases/heartbeats
# from drained replicas, and sm_fleet_* metric families exposed
if ! env JAX_PLATFORMS=cpu python scripts/load_sweep.py --elastic; then
    echo "check_tier1: FAIL — elastic-fleet smoke gate failed" >&2
    exit 1
fi

# perf-sentinel self-check (ISSUE 6): the regression gate itself is gated —
# the newest artifact of the synthetic fixture history must pass against it
# AND a synthetically degraded copy must trip the sentinel
if ! env JAX_PLATFORMS=cpu python scripts/perf_sentinel.py --self-check \
        --history 'tests/data/perf_history/bench_*.json'; then
    echo "check_tier1: FAIL — perf sentinel self-check failed" >&2
    exit 1
fi

# the ROADMAP.md tier-1 command, verbatim flags
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
    2>&1 | tee "$LOG"
pytest_rc=${PIPESTATUS[0]}

if [ "$pytest_rc" -ge 124 ]; then
    echo "check_tier1: FAIL — tier-1 run timed out or was killed (rc=$pytest_rc)" >&2
    exit 1
fi

PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)
echo "DOTS_PASSED=$PASSED (baseline $BASELINE)"

if [ "$PASSED" -lt "$BASELINE" ]; then
    echo "check_tier1: FAIL — $PASSED passed < baseline $BASELINE" >&2
    exit 1
fi
echo "check_tier1: OK — $PASSED passed >= baseline $BASELINE"

if [ "$RUN_CHAOS" -eq 1 ]; then
    echo "check_tier1: running chaos smoke stage (--chaos)"
    if ! env JAX_PLATFORMS=cpu python scripts/chaos_sweep.py --smoke; then
        echo "check_tier1: FAIL — chaos smoke stage failed" >&2
        exit 1
    fi
    echo "check_tier1: chaos smoke OK"
fi

if [ "$RUN_LOAD" -eq 1 ]; then
    echo "check_tier1: running load-sweep smoke stage"
    if ! env JAX_PLATFORMS=cpu python scripts/load_sweep.py --smoke; then
        echo "check_tier1: FAIL — load-sweep smoke stage failed" >&2
        exit 1
    fi
    echo "check_tier1: load-sweep smoke OK"
fi
