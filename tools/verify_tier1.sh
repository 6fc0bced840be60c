#!/usr/bin/env bash
# Tier-1 verification — the exact ROADMAP.md command, wrapped for CI.
#
# Runs the quick test tier on CPU, prints DOTS_PASSED (count of passing
# tests parsed from pytest's progress dots, the same metric the roadmap
# tracks), and exits non-zero on any failure.
#
# A second stage re-runs the comm-layer tests (tests/test_comm.py,
# tests/test_quantized_allreduce.py) with the 8-device CPU mesh forced
# at the SHELL level (JAX_PLATFORMS=cpu +
# --xla_force_host_platform_device_count=8) — the conftest sets the same
# env today, but the gradient-sync acceptance pins (fixed collective
# count, <=30% wire bytes, psum-tolerance numerics; see docs/comm.md)
# must not silently start skipping on their eight_devices fixture if
# that ever changes, and must run even when extra pytest args (e.g.
# `-m chaos`) filter them out of the main pass.
#
# A third stage drives the observability pipe end to end: the resilient
# example runs under injected chaos with --metrics-out, and the JSONL is
# asserted to parse, carry the bench-line schema with step/MFU/goodput
# keys, and reflect the injected skip count EXACTLY (docs/observability.md).
# Like the comm pass it hard-fails rather than silently skipping.
#
# A FLIGHT stage drives the crash-forensics path end to end
# (docs/observability.md): the resilient example runs under a
# persistent chaos NaN burst until the skip budget exhausts
# max_rollbacks (a RuntimeError by contract), and the stage asserts a
# flight dump exists, tools/flight_view.py parses it, and the dump's
# recorded skip/rollback counts EXACTLY match the JSONL goodput line.
#
# A fourth stage is the static-analysis gate (docs/analysis.md):
# tools/repo_lint.py greps apex_tpu/ for banned source patterns in
# jitted paths (incl. the sharding source rules: in_shardings=None,
# unpinned shard_map contractions), and tools/graph_lint.py builds the
# resilient example's ACTUAL compiled step and runs the
# apex_tpu.analysis passes over its jaxpr + optimized HLO — any
# ERROR-severity finding (host transfer, dropped donation, f64,
# collective mismatch) hard-fails.  A sharding/memory gate (ISSUE 9)
# then runs tools/shard_report.py against the same example on a MOCKED
# 8-device mesh (--xla_force_host_platform_device_count=8): the
# declared dp plan must prove out (params/scaler replicated, batch
# sharded over dp, only the declared gradient sync compiled) with zero
# ERRORs, and the static peak-HBM estimate must sit inside the 8 MiB
# budget without drifting to zero — both directions of drift fail.
# A kernel gate (ISSUE 10) then runs tools/kernel_lint.py over the
# three shipped Pallas kernels at their default configs (zero ERRORs,
# causal dead-tile waste < 0.15) and an attn_tune --prune --dry-run
# smoke: the compile-free cost model must keep the measured-best
# (1024, 1024) long-shape tile while eliminating >=30% of the sweep
# grid.
#
# A TRAIN stage proves the composable trainer (ISSUE 12,
# docs/training.md): tools/shard_report.py --target train builds the
# apex_tpu.train demo config at dp=2, tp=2, and dp=2 x tp=2 on the
# MOCKED 8-device mesh and must report zero ERRORs against the
# trainer's OWN derived rule table + collective plan (the compiled
# collective schedule EQUALS the declaration or the reshard pass
# fails), with a non-degenerate static peak inside the 64 MiB budget —
# drift in either direction (peak 0 = the estimator went blind; over
# budget = the build lied about memory) hard-fails.  The dp>=2 arms
# must come out mode=zero (the update-sharding heuristic genuinely
# chose ZeRO) with the flat optimizer state compiled SHARDED.
#
# A PERF stage guards the perf-observability contract
# (docs/observability.md "Attribution & roofline"):
#   1. the committed r03→r05 flash-attention flatline MUST be caught by
#      tools/bench_diff.py --fail-on-flat (and the same rounds must
#      pass the plain regression gate — no false positive);
#   2. short CPU bench configs (bench.py --config smoke / serve, plus
#      --config train3d --lint on the mocked 8-device mesh) run end to
#      end and their lines pass the schema gate against the committed
#      golden (key order, degenerate honesty vs the unit's dp=/tp=,
#      and the train3d rows' REQUIRED dp/tp >= 2 shapes);
#   3. tools/step_profile.py --target resilient emits
#      compute/collective/host-stall fractions summing to 1 +- 0.02
#      (the ISSUE 6 acceptance line).  The CPU has no peak on record,
#      so its roofline and MFU must read "not measured" here; the
#      roofline-vs-StepMeter MFU agreement within 5% is asserted where
#      it can be measured, in tests_tpu/test_step_profile.py.
#
# A SERVE stage drives the inference path end to end
# (docs/serving.md): the serve example trains a tiny GPT with the
# resilient runner, restores the checkpoint from disk (asserting the
# restored tree is bit-exact — the train->serve handoff), and serves it
# through the AOT engine + paged KV cache + continuous-batching
# scheduler.  The stage asserts the emitted JSONL carries TTFT and
# tokens-per-s serving metrics, and that tools/graph_lint.py --target
# serve reports ZERO ERRORs on the compiled prefill/decode steps.
# A span-accounting gate (ISSUE 8) then runs tools/serve_bench.py with
# --spans and feeds the dump through tools/timeline.py --json: every
# admitted request must have a complete span chain with exactly one
# terminal event, per-request TTFT components must sum to the measured
# TTFT within 1ms, the per-reason shed counters must sum to the total
# on both the artifact and the registry, and the merged Perfetto trace
# must carry real events.
# A prefix-cache gate (ISSUE 17) then replays an 85%-shared Poisson
# workload with the content-addressed prefix cache + chunked prefill
# armed and asserts the headline win AND its correctness escort:
# cache-hit p50 TTFT <= 0.3x cold-miss p50 at equal load, >= 50% of
# prefill FLOPs saved, every completed request's token stream
# bit-identical to a cache-disabled replay, and every
# PagePool.leak_check clean with the cache holding pages.
#
# An OPS stage drives the live ops plane end to end
# (docs/observability.md "Live ops plane", ISSUE 11): serve_bench runs
# a Poisson load with --ops-port 0 --spans under a PLANTED deadline
# storm (--slo-ttft-ms 1: every admission blows the TTFT objective).
# The gate asserts (1) the artifact's end-of-run HTTP scrape is
# OpenMetrics-valid (ometrics.parse_exposition) and carries
# TTFT/queue/goodput/watermark families whose values EQUAL the
# artifact's registry section (the scrape ran after the final drain);
# (2) the fast-burn multi-window SLO alert fired as a critical
# HealthEvent AND landed as a health/slo_ttft instant in the span dump
# and the merged Perfetto trace; (3) the fake-provider memstats
# cross-check reconciles cleanly on the honest run, and a second run
# with --memstats-fake-scale 2.0 (a planted static-vs-live drift) is
# FLAGGED with a finding naming the governing program.
#
# A SERVE-CHAOS stage proves the serving resilience layer end to end
# (docs/serving.md "Failure semantics & degradation ladder", ISSUE 14):
# tools/serve_chaos_drill.py runs a fault-free Poisson reference, then
# the same load under an APEX_TPU_CHAOS-grammar storm firing all four
# serving chaos sites (serve.prefill raise, serve.decode raise+nan,
# serve.admission raise, serve.kv_alloc fail), then a deterministic
# overload-ladder probe (queue-cap fast-reject + max-new-tokens clamp)
# and a graceful drain.  The drill hard-fails unless: zero process
# deaths (it finishing IS the proof), PagePool.leak_check clean after
# every fault with the pool exactly empty at the end, every request in
# exactly one accounted terminal state, p99 TTFT <= 2x the fault-free
# reference, every injected fault visible on its ledger counter
# (engine_faults/rebuilds, shed_poisoned, admission/kv_alloc faults),
# the ladder rejecting exactly the over-cap burst excess, and the
# drain report clean.  The gate then re-proves chain completeness from
# the span dump via tools/timeline.py --json and re-asserts the
# headline numbers from the artifact.  The artifact is handed to the
# PERF stage (APEX_TPU_SERVE_CHAOS_ARTIFACT) so bench.py --config
# serve emits its serve_chaos_* golden rows from the SAME storm
# instead of paying a second one — which is why SERVE-CHAOS runs
# before PERF.
#
# A GOODPUT stage proves the preemptible-fleet I/O plane end to end
# (ISSUE 13, docs/goodput.md): tools/goodput_drill.py runs the
# resilient example's real programs through an APEX_TPU_CHAOS-style
# preemption storm — resumable-stream-fed, async-engine-checkpointed —
# and the gate asserts goodput >= 99%, a bit-identical resumed loss
# trajectory, checkpoint stall < 1% of wall time, intact-previous-
# checkpoint after a planted mid-write kill (tmp debris + markerless
# half-written step dir), ckpt/* spans on the timeline, and zero
# goodput_rules watchdog pages.  The same drill's numbers land as
# gated bench rows (bench.py --config goodput in the PERF stage reuses
# the GOODPUT stage's evidence artifact — which is why GOODPUT runs
# first — against the committed golden) so they can never go flat
# silently.
#
# A FLEET stage proves the multi-replica control plane end to end
# (docs/serving.md "Fleet operations", ISSUE 16): tools/fleet_drill.py
# runs a fault-free fixed-size fleet reference, then the same seeded
# Poisson load — with a 5x arrival spike — through an autoscaled fleet
# under an APEX_TPU_CHAOS-grammar storm firing all three fleet sites
# (fleet.router raise, fleet.replica_crash kill, fleet.preempt notice)
# plus a mid-load zero-downtime rolling deploy.  The drill hard-fails
# unless: every request reaches exactly one fleet-wide terminal, zero
# open spans, per-replica PagePool leak_check clean, p99 TTFT <= 2x
# the reference, every injected fault pinned on its fleet/* ledger
# counter, the re-route ledger agrees across router and replicas,
# >= 1 autoscaler scale-out AND scale-in on the health timeline, the
# rolling deploy updates every replica with ZERO accepted requests
# lost, and every replica's ops server binds a distinct port whose
# scrapes aggregate.  The gate then re-proves chain completeness from
# the span dump via tools/timeline.py --json, and hands the artifact
# to the PERF stage (APEX_TPU_FLEET_ARTIFACT) so bench.py --config
# fleet emits its fleet_* golden rows from the SAME storm — which is
# why FLEET runs before PERF.
#
# A CANARY stage proves canary-gated deploys end to end
# (docs/serving.md "Canary deploys", ISSUE 20): tools/canary_drill.py
# asserts golden-probe fingerprints are bit-exact across a
# same-weights rebuild yet flip on a SINGLE corrupted weight bit,
# runs clean canary deploys across independent seeds (ZERO false
# fail verdicts by contract — the one-sided drift tests + min-sample
# honesty floor must not page on the canary hold's own load skew),
# then plants a NaN-poisoned + decode-throttled deploy and asserts
# the drift verdict FAILS inside the window, the deploy halts and
# rolls the canary back to the incumbent weights (rollback
# fingerprint bit-exact), fleet/deploys_rolled_back bumps, ZERO
# requests are lost, and bad-weight exposure stays within the canary
# fraction.  The gate re-proves the exposure bound from the span dump
# alone via tools/timeline.py --json (account_canary over the
# validated `canary` routing annotations), and hands the artifact to
# the PERF stage (APEX_TPU_CANARY_ARTIFACT) so bench.py --config
# fleet emits the fleet_canary_* golden rows from the SAME drill —
# which is why CANARY runs before PERF.
#
# Usage:
#   tools/verify_tier1.sh              # quick tier + comm + obs + flight + lint + train + goodput + serve-chaos + fleet + canary + perf + serve + ops
#   tools/verify_tier1.sh -m chaos     # extra pytest args are passed through
#
# Env:
#   T1_LOG      log path        (default /tmp/_t1.log)
#   T1_TIMEOUT  seconds         (default 870)
#   T1_SKIP_COMM=1              skip the dedicated comm pass
#   T1_SKIP_OBS=1               skip the observability pass
#   T1_SKIP_FLIGHT=1            skip the flight-recorder pass
#   T1_SKIP_LINT=1              skip the static-analysis pass
#   T1_SKIP_TRAIN=1             skip the composable-trainer pass
#   T1_SKIP_PERF=1              skip the perf-gate pass
#   T1_SKIP_SERVE=1             skip the serving pass
#   T1_SKIP_OPS=1               skip the live-ops-plane pass
#   T1_SKIP_GOODPUT=1           skip the goodput storm-drill pass
#   T1_SKIP_SERVECHAOS=1        skip the serving chaos-drill pass
#   T1_SKIP_FLEET=1             skip the fleet control-plane drill pass
#   T1_SKIP_CANARY=1            skip the canary-deploy drill pass

set -o pipefail

REPO_ROOT="$(cd "$(dirname "$0")/.." && pwd)"
LOG="${T1_LOG:-/tmp/_t1.log}"
TIMEOUT="${T1_TIMEOUT:-870}"

cd "$REPO_ROOT" || exit 2
rm -f "$LOG"

timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors \
    -p no:cacheprovider -p no:xdist -p no:randomly \
    "$@" 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}

dots=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)
echo "DOTS_PASSED=$dots"

comm_rc=0
if [ "${T1_SKIP_COMM:-0}" != "1" ]; then
    timeout -k 10 "$TIMEOUT" env JAX_PLATFORMS=cpu \
        XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        python -m pytest tests/test_comm.py tests/test_quantized_allreduce.py \
        -q -m 'not slow' \
        -p no:cacheprovider -p no:xdist -p no:randomly \
        2>&1 | tee -a "$LOG"
    comm_rc=${PIPESTATUS[0]}
    # the acceptance pins may not pass by skipping: fail on any skips
    # (match the skipped count anywhere in the summary — an all-skipped
    # run prints "N skipped in ..." with no "passed" token at all)
    if tail -n 3 "$LOG" | grep -aqE '(^|[ ,])[0-9]+ skipped'; then
        echo "TIER1-COMM: FAIL (comm tests skipped — 8-device mesh missing?)"
        comm_rc=1
    elif [ "$comm_rc" -eq 0 ]; then
        echo "TIER1-COMM: PASS"
    else
        echo "TIER1-COMM: FAIL (pytest rc=$comm_rc)"
    fi
fi

obs_rc=0
if [ "${T1_SKIP_OBS:-0}" != "1" ]; then
    OBS_OUT="$(mktemp /tmp/_t1_obs.XXXXXX.jsonl)"
    OBS_DIR="$(mktemp -d /tmp/_t1_obs_ckpt.XXXXXX)"
    # grads:nan@7,8 -> exactly 2 skipped steps, 0 rollbacks; the JSONL
    # goodput line must reproduce those counts (ISSUE 3 acceptance)
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        APEX_TPU_CHAOS="grads:nan@7,8" \
        python examples/simple/resilient/train_resilient.py \
        --steps 20 --save-every 5 --dir "$OBS_DIR" \
        --metrics-out "$OBS_OUT" 2>&1 | tail -n 4 | tee -a "$LOG"
    obs_rc=${PIPESTATUS[0]}
    if [ "$obs_rc" -eq 0 ]; then
        python - "$OBS_OUT" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
recs = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
assert recs, "metrics JSONL is empty"
for r in recs:
    assert list(r)[:4] == ["metric", "value", "unit", "vs_baseline"], r
    assert "step" in r, f"telemetry line without step key: {r}"
metrics = {r["metric"] for r in recs}
for need in ("train/step_time_ms", "train/goodput",
             "train/loss", "amp/loss_scale", "guard/skipped"):
    assert need in metrics, f"missing metric {need}; have {sorted(metrics)}"
final = [r for r in recs if r["metric"] == "train/goodput" and "skipped" in r]
assert final, "no consolidated goodput line with event counts"
g = final[-1]
assert g["skipped"] == 2, f"goodput line skipped={g['skipped']}, chaos injected 2"
assert g["rollbacks"] == 0, f"goodput line rollbacks={g['rollbacks']}, expected 0"
assert g["value"] == (g["accepted"] - g["discarded"]) / (g["accepted"] + g["skipped"])
print(f"observability JSONL OK: {len(recs)} records, goodput={g['value']:.3f} "
      f"(skipped={g['skipped']}, rollbacks={g['rollbacks']})")
PYEOF
        obs_rc=${PIPESTATUS[0]}
    fi
    rm -rf "$OBS_DIR"
    if [ "$obs_rc" -eq 0 ]; then
        rm -f "$OBS_OUT"
        echo "TIER1-OBS: PASS"
    else
        # keep the JSONL that failed the assertions — it IS the evidence
        echo "TIER1-OBS: FAIL (rc=$obs_rc; metrics kept at $OBS_OUT)"
    fi
fi

flight_rc=0
if [ "${T1_SKIP_FLIGHT:-0}" != "1" ]; then
    FL_OUT="$(mktemp /tmp/_t1_flight.XXXXXX.jsonl)"
    FL_DIR="$(mktemp -d /tmp/_t1_flight_ckpt.XXXXXX)"
    # 5 consecutive NaN steps x (1 + max_rollbacks=3 replays) -> the
    # skip budget (rollback_after=5) exhausts and run_resilient raises;
    # the example must STILL leave a parseable black box.  Expected
    # ledger: skipped=20, rollbacks=3, in BOTH artifacts.
    timeout -k 10 300 env JAX_PLATFORMS=cpu \
        APEX_TPU_CHAOS="grads:nan@10,11,12,13,14" \
        python examples/simple/resilient/train_resilient.py \
        --steps 30 --save-every 5 --dir "$FL_DIR" \
        --metrics-out "$FL_OUT" 2>&1 | tail -n 3 | tee -a "$LOG"
    example_rc=${PIPESTATUS[0]}
    if [ "$example_rc" -eq 0 ]; then
        echo "TIER1-FLIGHT: example was expected to DIE (skip budget)" \
            | tee -a "$LOG"
        flight_rc=1
    else
        DUMP=$(ls "$FL_DIR"/flight/flight_*.json 2>/dev/null | tail -n 1)
        if [ -z "$DUMP" ]; then
            echo "TIER1-FLIGHT: no flight dump under $FL_DIR/flight" \
                | tee -a "$LOG"
            flight_rc=1
        else
            python tools/flight_view.py "$DUMP" --json 2>&1 | tee -a "$LOG"
            flight_rc=${PIPESTATUS[0]}
        fi
    fi
    if [ "$flight_rc" -eq 0 ]; then
        python - "$DUMP" "$FL_OUT" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
dump = json.load(open(sys.argv[1]))
recs = [json.loads(l) for l in open(sys.argv[2]) if l.strip()]
final = [r for r in recs if r["metric"] == "train/goodput" and "skipped" in r]
assert final, "no consolidated goodput line in the JSONL"
g = final[-1]
fg = dump.get("goodput") or {}
assert "skip budget exhausted" in dump["reason"], dump["reason"]
# the black box and the telemetry stream must tell ONE story
for key in ("accepted", "skipped", "discarded", "rollbacks", "retries"):
    assert fg.get(key) == g[key], (
        f"flight {key}={fg.get(key)} vs goodput line {g[key]}")
assert g["skipped"] == 20 and g["rollbacks"] == 3, g
frames = dump["frames"]
assert frames, "flight dump has no frames"
tail = frames[-5:]
assert all(f["skipped"] for f in tail), "last frames must be the fatal streak"
fm = dump["final"]["metrics"]
assert fm.get("guard/consecutive_skips") == 5.0, fm
assert fm.get("guard/found_inf") == 1.0, fm
print(f"flight dump OK: reason={dump['reason'][:40]!r}... "
      f"skipped={fg['skipped']} rollbacks={fg['rollbacks']} "
      f"(== JSONL goodput line)")
PYEOF
        flight_rc=${PIPESTATUS[0]}
    fi
    if [ "$flight_rc" -eq 0 ]; then
        rm -rf "$FL_DIR"
        rm -f "$FL_OUT"
        echo "TIER1-FLIGHT: PASS"
    else
        # keep the artifacts that failed the assertions — the evidence
        echo "TIER1-FLIGHT: FAIL (rc=$flight_rc; metrics at $FL_OUT," \
            "dump dir $FL_DIR)"
    fi
fi

lint_rc=0
if [ "${T1_SKIP_LINT:-0}" != "1" ]; then
    # source-level lint: banned patterns in jitted paths (fast, no jax)
    python tools/repo_lint.py 2>&1 | tee -a "$LOG"
    lint_rc=${PIPESTATUS[0]}
    if [ "$lint_rc" -eq 0 ]; then
        # concurrency + replay-purity lint: lock discipline over every
        # threaded class and purity over the replay-critical modules —
        # any ERROR finding exits 1 (also jax-free)
        CLINT_JSON="${T1_CLINT_JSON:-/tmp/_t1_concurrency_lint.json}"
        python tools/concurrency_lint.py --json "$CLINT_JSON" \
            2>&1 | tee -a "$LOG"
        lint_rc=${PIPESTATUS[0]}
    fi
    if [ "$lint_rc" -eq 0 ]; then
        # graph lint: the resilient example's compiled step must carry
        # zero ERROR findings (exit 1 otherwise — the acceptance gate)
        LINT_JSON="${T1_LINT_JSON:-/tmp/_t1_graph_lint.json}"
        timeout -k 10 300 env JAX_PLATFORMS=cpu \
            python tools/graph_lint.py --target resilient \
            --json "$LINT_JSON" 2>&1 | tee -a "$LOG"
        lint_rc=${PIPESTATUS[0]}
    fi
    if [ "$lint_rc" -eq 0 ]; then
        # sharding & memory gate (ISSUE 9): prove the declared dp plan
        # on a mocked 8-device mesh — zero ERRORs, budget headroom, and
        # a non-degenerate estimate (peak 0 would mean the estimator
        # silently stopped seeing buffers: drift in EITHER direction
        # fails)
        SHARD_JSON="${T1_SHARD_JSON:-/tmp/_t1_shard_report.json}"
        SHARD_BUDGET=$((8 * 1024 * 1024))
        timeout -k 10 300 env JAX_PLATFORMS=cpu \
            XLA_FLAGS="--xla_force_host_platform_device_count=8" \
            python tools/shard_report.py --target resilient \
            --budget "$SHARD_BUDGET" --json "$SHARD_JSON" \
            2>&1 | tail -n 6 | tee -a "$LOG"
        lint_rc=${PIPESTATUS[0]}
        if [ "$lint_rc" -eq 0 ]; then
            python - "$SHARD_JSON" "$SHARD_BUDGET" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
d = json.load(open(sys.argv[1]))
budget = int(sys.argv[2])
assert d["errors"] == 0, f"shard report carries {d['errors']} ERROR(s)"
peak = d["peak_hbm_bytes"]
assert 0 < peak <= budget, f"peak {peak} outside (0, {budget}] — estimator drift"
rows = {(r["program"], r["name"]): r for r in d["shard_plan"]}
w = rows[("resilient/compute_grads", "params/w")]
assert w["verdict"] == "ok" and w["sharding"] == "replicated", w
b0 = rows[("resilient/compute_grads", "batch/0")]
assert b0["verdict"] == "ok" and "devices=" in b0["sharding"], b0
for name in ("sharding", "reshard", "memory"):
    assert name in d["pass_timings"], d["pass_timings"]
print(f"shard report OK: peak_hbm={peak} bytes (budget {budget}), "
      f"{len(d['shard_plan'])} plan rows, dp plan proven on the 8-device mesh")
PYEOF
            lint_rc=${PIPESTATUS[0]}
        fi
    fi
    if [ "$lint_rc" -eq 0 ]; then
        # kernel gate (ISSUE 10, docs/analysis.md "Kernel passes"):
        # the three shipped Pallas kernels at their default configs
        # must carry zero ERROR findings (VMEM/tiling/coverage) and
        # the causal flash default must waste <15% of its live-tile
        # FLOPs on masked elements
        KLINT_JSON="${T1_KLINT_JSON:-/tmp/_t1_kernel_lint.json}"
        timeout -k 10 300 env JAX_PLATFORMS=cpu \
            python tools/kernel_lint.py --json "$KLINT_JSON" \
            --max-dead-tile 0.15 2>&1 | tail -n 10 | tee -a "$LOG"
        lint_rc=${PIPESTATUS[0]}
    fi
    if [ "$lint_rc" -eq 0 ]; then
        # attn_tune prune smoke: the compile-free cost model must keep
        # the measured-best (1024, 1024) long-shape tile while
        # eliminating >=30% of the default sweep grid — all without
        # touching a device
        PRUNE_OUT="$(mktemp /tmp/_t1_prune.XXXXXX.log)"
        timeout -k 10 300 env JAX_PLATFORMS=cpu \
            python tools/attn_tune.py --prune --dry-run --shapes long \
            > "$PRUNE_OUT" 2>&1
        lint_rc=$?
        if [ "$lint_rc" -eq 0 ]; then
            python - "$PRUNE_OUT" <<'PYEOF' 2>&1 | tee -a "$LOG"
import re, sys
text = open(sys.argv[1]).read()
sweeps = [(int(k), int(t)) for k, t in re.findall(r"keep (\d+)/(\d+)", text)]
assert sweeps, "no prune summary in attn_tune --dry-run output"
for kept, total in sweeps:
    assert total - kept >= 0.3 * total, (
        f"prune eliminated only {total - kept}/{total} cells (<30%)")
assert re.search(r"^ *KEEP +1024 +1024", text, re.M), (
    "prune dropped the known-good (1024, 1024) long-shape config")
print(f"attn_tune prune smoke OK: kept {sweeps} of the default grid, "
      "(1024, 1024) survives")
PYEOF
            lint_rc=${PIPESTATUS[0]}
        fi
        if [ "$lint_rc" -eq 0 ]; then
            rm -f "$PRUNE_OUT"
        else
            echo "TIER1-LINT: attn_tune prune smoke failed (output at" \
                "$PRUNE_OUT)" | tee -a "$LOG"
        fi
    fi
    if [ "$lint_rc" -eq 0 ]; then
        echo "TIER1-LINT: PASS"
    else
        echo "TIER1-LINT: FAIL (rc=$lint_rc; findings in ${LINT_JSON:-repo_lint output} / ${CLINT_JSON:-concurrency_lint} / ${SHARD_JSON:-shard_report})"
    fi
fi

train_rc=0
if [ "${T1_SKIP_TRAIN:-0}" != "1" ]; then
    TRAIN_BUDGET=$((64 * 1024 * 1024))
    for spec in "2 1 zero" "1 2 ddp" "2 2 zero"; do
        set -- $spec
        TDP=$1; TTP=$2; TMODE=$3
        [ "$train_rc" -ne 0 ] && break
        TRAIN_JSON="$(mktemp /tmp/_t1_train_${TDP}x${TTP}.XXXXXX.json)"
        timeout -k 10 300 env JAX_PLATFORMS=cpu \
            XLA_FLAGS="--xla_force_host_platform_device_count=8" \
            python tools/shard_report.py --target train \
            --dp "$TDP" --tp "$TTP" --budget "$TRAIN_BUDGET" \
            --json "$TRAIN_JSON" 2>&1 | tail -n 4 | tee -a "$LOG"
        train_rc=${PIPESTATUS[0]}
        if [ "$train_rc" -eq 0 ]; then
            python - "$TRAIN_JSON" "$TRAIN_BUDGET" "$TDP" "$TTP" "$TMODE" \
                <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
d = json.load(open(sys.argv[1]))
budget, dp, tp, mode = (int(sys.argv[2]), int(sys.argv[3]),
                        int(sys.argv[4]), sys.argv[5])
assert d["errors"] == 0, f"trainer report carries {d['errors']} ERROR(s)"
assert d["target"].endswith(f"dp{dp}tp{tp}/{mode}"), d["target"]
peak = d["peak_hbm_bytes"]
assert 0 < peak <= budget, f"peak {peak} outside (0, {budget}] — drift"
for name in ("sharding", "reshard", "memory"):
    assert name in d["pass_timings"], d["pass_timings"]
rows = {r["name"]: r for r in d["shard_plan"]}
assert all(r["verdict"] == "ok" for r in rows.values()), rows
if mode == "zero":
    # the heuristic chose ZeRO and the flat optimizer state COMPILED
    # sharded — the headline feature, proven from the artifact
    m = rows["state/opt/master"]
    assert "devices=" in m["sharding"], m
if tp > 1:
    assert "devices=" in rows["state/params/w1"]["sharding"], rows
print(f"train dp={dp} tp={tp} OK: mode={mode}, peak_hbm={peak} bytes, "
      f"{len(rows)} plan rows all conformant, schedule == declaration")
PYEOF
            train_rc=${PIPESTATUS[0]}
        fi
        if [ "$train_rc" -eq 0 ]; then
            rm -f "$TRAIN_JSON"
        else
            echo "TIER1-TRAIN: dp=$TDP tp=$TTP failed (report at" \
                "$TRAIN_JSON)" | tee -a "$LOG"
        fi
    done
    if [ "$train_rc" -eq 0 ]; then
        echo "TIER1-TRAIN: PASS"
    else
        echo "TIER1-TRAIN: FAIL (rc=$train_rc)"
    fi
fi

goodput_rc=0
if [ "${T1_SKIP_GOODPUT:-0}" != "1" ]; then
    # GOODPUT gate (ISSUE 13, docs/goodput.md): an APEX_TPU_CHAOS-style
    # preemption storm through the resilient example's REAL programs,
    # fed by the resumable stream, saved by the async engine.  The
    # drill itself hard-fails unless goodput >= 99%, the resumed loss
    # trajectory is bit-identical to the uninterrupted reference,
    # checkpoint stall < 1% of wall time, the planted mid-write kill
    # (orbax tmp debris + a markerless half-written step dir) leaves
    # the previous checkpoint as the resume anchor, ckpt spans land on
    # the timeline, and the goodput_rules watchdog stays quiet.  The
    # artifact assertions below re-prove the verdict from the evidence.
    GP_JSON="$(mktemp /tmp/_t1_goodput.XXXXXX.json)"
    GP_DIR="$(mktemp -d /tmp/_t1_goodput_drill.XXXXXX)"
    # APEX_TPU_LOCKSAN=1 arms the runtime lock-order sanitizer for the
    # whole storm: the artifact's "locksan" section must come back
    # armed, with acquisitions recorded and ZERO cycles
    timeout -k 10 420 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
        APEX_TPU_LOCKSAN=1 \
        python tools/goodput_drill.py --steps 60 --preempt-every 12 \
        --dir "$GP_DIR" --json "$GP_JSON" 2>&1 | tail -n 5 | tee -a "$LOG"
    goodput_rc=${PIPESTATUS[0]}
    if [ "$goodput_rc" -eq 0 ]; then
        python - "$GP_JSON" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
a = json.load(open(sys.argv[1]))
assert a["goodput"] >= 0.99, f"goodput {a['goodput']} under the 99% floor"
lt = a["loss_trajectory"]
assert lt["bit_exact"] and lt["max_abs_drift"] == 0.0, lt
assert lt["storm_steps"] == lt["ref_steps"] == a["steps"], lt
assert a["ckpt"]["stall_frac"] < 0.01, a["ckpt"]
assert a["accountant"]["resumes"] >= 3, a["accountant"]  # the storm ran
assert a["accountant"]["retries"] >= 1, a["accountant"]  # fault healed
pm = a["planted_midwrite"]
assert pm["previous_intact"] and pm["resume_ok"], pm
sc = a["stream_cursor"]
assert sc["restored_next_batch"] == sc["expected"], sc
assert a["spans"]["ckpt_write"] > 0 and a["spans"]["ckpt_snapshot"] > 0
assert a["watchdog_pages"] == [], a["watchdog_pages"]
ls = a["locksan"]
assert ls["armed"], "LOCKSAN was not armed for the drill"
assert ls["cycles"] == [], f"lock-order cycles: {ls['cycles']}"
assert ls["locks"], "sanitizer saw no TrackedLock acquisitions"
print(f"GOODPUT artifact OK: goodput={a['goodput']:.4f} over "
      f"{a['invocations']} invocations ({a['accountant']['resumes']} "
      f"preemption resumes), stall={a['ckpt']['stall_frac']:.4%}, "
      f"loss drift {lt['max_abs_drift']} over {lt['ref_steps']} steps, "
      f"mid-write plant ignored (anchor step {pm['latest_before']})")
PYEOF
        goodput_rc=${PIPESTATUS[0]}
    fi
    if [ "$goodput_rc" -eq 0 ]; then
        # keep the artifact: the PERF stage's `bench.py --config
        # goodput` reuses it (APEX_TPU_GOODPUT_ARTIFACT) instead of
        # paying a second full storm drill for the same numbers
        rm -rf "$GP_DIR"
        echo "TIER1-GOODPUT: PASS"
    else
        echo "TIER1-GOODPUT: FAIL (rc=$goodput_rc; artifact at $GP_JSON," \
            "drill dir $GP_DIR)"
    fi
fi

servechaos_rc=0
if [ "${T1_SKIP_SERVECHAOS:-0}" != "1" ]; then
    SC_JSON="$(mktemp /tmp/_t1_servechaos.XXXXXX.json)"
    SC_SPANS="$(mktemp /tmp/_t1_servechaos_spans.XXXXXX.json)"
    SC_TRACE="$(mktemp /tmp/_t1_servechaos_trace.XXXXXX.json)"
    # the drill hard-fails on its own acceptance set (deaths, leaks,
    # terminals, p99 bound, ledger pins, ladder, drain) — see the
    # header comment
    timeout -k 10 420 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
        python tools/serve_chaos_drill.py \
        --json "$SC_JSON" --spans "$SC_SPANS" \
        2>&1 | tail -n 7 | tee -a "$LOG"
    servechaos_rc=${PIPESTATUS[0]}
    if [ "$servechaos_rc" -eq 0 ]; then
        # chain completeness re-proven from the span dump: every storm
        # + probe + drain request walked
        # queued -> ... [retrying ...] -> exactly one terminal
        timeout -k 10 120 env JAX_PLATFORMS=cpu \
            python tools/timeline.py --spans "$SC_SPANS" \
            --out "$SC_TRACE" --json 2>&1 | tee -a "$LOG"
        servechaos_rc=${PIPESTATUS[0]}
    fi
    if [ "$servechaos_rc" -eq 0 ]; then
        python - "$SC_JSON" "$SC_SPANS" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
a = json.load(open(sys.argv[1]))
spans = json.load(open(sys.argv[2]))
assert a["process_deaths"] == 0
assert len(a["chaos_sites"]) == 4, a["chaos_sites"]  # all four serve sites
t = a["terminals"]
assert t["accounted"] and t["completed"] + t["shed"] == t["offered"], t
assert t["open_spans"] == 0, t
p = a["pages"]
assert p["pool_in_use_end"] == 0, p
assert p["leak_checks_run"] > 0, p
infl = a["p99_ttft_inflation"]
assert infl == infl and infl <= 2.0, f"p99 inflation {infl}"
assert a["engine"]["rebuilds"] >= 1, a["engine"]
reg = a["registry"]
assert reg.get("serve/shed_poisoned", 0) >= 1, "quarantine never fired"
assert reg.get("serve/retries", 0) >= 1, "no re-admission retries"
probe = a["overload_probe"]
assert probe["queue_full"] == probe["burst"] - probe["queue_cap"], probe
assert probe["clamped"] >= 2, probe
d = a["drain"]
assert d["drained"] and d["pool_in_use"] == 0 and d["shed_draining"] >= 1, d
# the retrying recovery phase is ON the span record, not just counted
names = {e["name"] for e in spans["spans"]}
assert "req/retrying" in names, sorted(names)
assert "req/clamped" in names, sorted(names)
print(f"SERVE-CHAOS artifact OK: {t['completed']}/{t['offered']} "
      f"terminal-accounted, p99 inflation {infl:.2f}x (<=2x), "
      f"{a['engine']['rebuilds']} rebuild(s), "
      f"{reg.get('serve/shed_poisoned', 0):.0f} quarantined, "
      f"{p['leak_checks_run']} leak checks clean")
PYEOF
        servechaos_rc=${PIPESTATUS[0]}
    fi
    if [ "$servechaos_rc" -eq 0 ]; then
        # keep SC_JSON: the PERF stage's bench --config serve reuses it
        # (APEX_TPU_SERVE_CHAOS_ARTIFACT) instead of a second storm
        rm -f "$SC_SPANS" "$SC_TRACE"
        echo "TIER1-SERVECHAOS: PASS"
    else
        echo "TIER1-SERVECHAOS: FAIL (rc=$servechaos_rc; artifacts at" \
            "$SC_JSON $SC_SPANS $SC_TRACE)"
    fi
fi

fleet_rc=0
if [ "${T1_SKIP_FLEET:-0}" != "1" ]; then
    FL_JSON="$(mktemp /tmp/_t1_fleet.XXXXXX.json)"
    FL_SPANS="$(mktemp /tmp/_t1_fleet_spans.XXXXXX.json)"
    # the drill hard-fails on its own acceptance set (terminals, leaks,
    # ledger pins, scale-out+in, zero-loss deploy, p99 bound, ops
    # aggregation) — see the header comment
    timeout -k 10 600 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
        python tools/fleet_drill.py \
        --json "$FL_JSON" --spans "$FL_SPANS" \
        2>&1 | tail -n 8 | tee -a "$LOG"
    fleet_rc=${PIPESTATUS[0]}
    if [ "$fleet_rc" -eq 0 ]; then
        # chain completeness re-proven from the span dump: every storm
        # request walked queued -> [routed/retrying hops] -> exactly
        # one fleet-wide terminal, across every replica it visited
        timeout -k 10 120 env JAX_PLATFORMS=cpu \
            python tools/timeline.py --spans "$FL_SPANS" --json \
            2>&1 | tail -n 3 | tee -a "$LOG"
        fleet_rc=${PIPESTATUS[0]}
    fi
    if [ "$fleet_rc" -eq 0 ]; then
        python - "$FL_JSON" "$FL_SPANS" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
a = json.load(open(sys.argv[1]))
spans = json.load(open(sys.argv[2]))
assert a["process_deaths"] == 0
assert len(a["chaos_sites"]) == 3, a["chaos_sites"]  # all three fleet sites
t = a["terminals"]
assert t["accounted"] and t["completed"] + t["shed"] == t["offered"], t
assert t["open_spans"] == 0 and t["span_drops"] == 0, t
assert all(v == 0 for v in a["pages"]["per_replica_in_use"].values()), \
    a["pages"]
infl = a["p99_ttft_inflation"]
assert infl == infl and infl <= 2.0, f"p99 inflation {infl}"
fr = a["fleet_registry"]
assert fr.get("fleet/replica_crashes", 0) >= 1, fr
assert fr.get("fleet/preempts", 0) >= 1, fr
assert fr.get("fleet/router_faults", 0) >= 1, fr
assert fr.get("fleet/scale_out", 0) >= 1, fr
assert fr.get("fleet/scale_in", 0) >= 1, fr
sc = a["autoscaler"]
assert sc["scale_out_events"] >= 1 and sc["scale_in_events"] >= 1, sc
assert a["deploys"] and all(
    d["lost_requests"] == 0 and d["updated"] for d in a["deploys"]
), a["deploys"]
# the re-route ledger agrees fleet-wide: router hops == replica sheds
assert a["aggregated_serve"].get("serve/shed_rerouted", 0) \
    == fr.get("fleet/rerouted", 0), (a["aggregated_serve"], fr)
ops = a["ops"]
assert ops["all_bound"] and ops["distinct_ports"], ops
assert ops["aggregated_sources"] == ops["servers"], ops
# the routed hop phase is ON the span record, not just counted
names = {e["name"] for e in spans["spans"]}
assert "req/routed" in names, sorted(names)
print(f"FLEET artifact OK: {t['completed']}/{t['offered']} "
      f"terminal-accounted across {len(a['replicas'])} replicas, "
      f"p99 inflation {infl:.2f}x (<=2x), crashes="
      f"{fr.get('fleet/replica_crashes', 0):.0f} preempts="
      f"{fr.get('fleet/preempts', 0):.0f} rerouted="
      f"{fr.get('fleet/rerouted', 0):.0f}, scale out/in="
      f"{sc['scale_out_events']}/{sc['scale_in_events']}, "
      f"{len(a['deploys'])} deploy(s) lost 0")
PYEOF
        fleet_rc=${PIPESTATUS[0]}
    fi
    if [ "$fleet_rc" -eq 0 ]; then
        # keep FL_JSON: the PERF stage's bench --config fleet reuses it
        # (APEX_TPU_FLEET_ARTIFACT) instead of a second storm
        rm -f "$FL_SPANS"
        echo "TIER1-FLEET: PASS"
    else
        echo "TIER1-FLEET: FAIL (rc=$fleet_rc; artifacts at" \
            "$FL_JSON $FL_SPANS)"
    fi
fi

canary_rc=0
if [ "${T1_SKIP_CANARY:-0}" != "1" ]; then
    CN_JSON="$(mktemp /tmp/_t1_canary.XXXXXX.json)"
    CN_SPANS="$(mktemp /tmp/_t1_canary_spans.XXXXXX.json)"
    # the drill hard-fails on its own acceptance set (fingerprint
    # bit-exactness + single-bit sensitivity, zero false verdicts on
    # clean deploys, planted-regression detection + bit-exact
    # rollback, zero lost requests, exposure bound) — see its header
    timeout -k 10 600 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
        python tools/canary_drill.py \
        --json "$CN_JSON" --spans "$CN_SPANS" \
        2>&1 | tail -n 10 | tee -a "$LOG"
    canary_rc=${PIPESTATUS[0]}
    if [ "$canary_rc" -eq 0 ]; then
        # the exposure bound re-proven from the span dump alone: every
        # canary-annotated routing hop falls inside a deploy window,
        # and per window canary hops <= frac * routed + 1
        timeout -k 10 120 env JAX_PLATFORMS=cpu \
            python tools/timeline.py --spans "$CN_SPANS" --json \
            > /tmp/_t1_canary_timeline.json 2>>"$LOG"
        canary_rc=$?
    fi
    if [ "$canary_rc" -eq 0 ]; then
        python - "$CN_JSON" /tmp/_t1_canary_timeline.json \
            <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
a = json.load(open(sys.argv[1]))
tl = json.load(open(sys.argv[2]))
fp = a["fingerprints"]
assert fp["rebuild_bit_exact"], fp
assert fp["single_bit_flips_digest"], fp
assert fp["restore_matches"], fp
assert a["false_positives"] == 0, a["false_positives"]
frac = a["config"]["canary_frac"]
for run in a["clean_runs"]:
    d = run["deploys"][-1]
    assert d["canary"]["verdict"] == "pass", (run["label"], d)
    assert d["lost_requests"] == 0, (run["label"], d)
reg = a["regression"]
d = reg["deploys"][-1]
c = d["canary"]
assert d["rolled_back"] and c["verdict"] == "fail", d
assert reg["rolled_back"] == 1, reg["rolled_back"]
assert d["lost_requests"] == 0, d
assert c["rollback_digest"] == reg["incumbent_digest"], c
assert a["detect_ticks"] is not None and a["detect_ticks"] > 0
# the timeline's independent re-derivation: one pass + one fail
# window, both within the canary fraction
assert tl["ok"], tl["violations"]
wins = tl["canary"]["windows"]
verdicts = sorted(w["verdict"] for w in wins)
assert verdicts == ["fail", "pass"], wins
for w in wins:
    assert w["closed"], w
    assert w["canary_routed"] <= w["frac"] * w["routed"] + 1, w
    assert w["frac"] == frac, (w, frac)
print(f"CANARY artifact OK: fingerprint bit-exact + single-bit "
      f"sensitive, {len(a['clean_runs'])} clean deploys 0 false "
      f"verdicts, regression detected in {a['detect_ticks']} ticks "
      f"and rolled back bit-exact, exposure "
      f"{max(w['exposure_frac'] for w in wins):.3f} <= {frac} "
      f"re-proven from {len(wins)} span-dump windows")
PYEOF
        canary_rc=${PIPESTATUS[0]}
    fi
    if [ "$canary_rc" -eq 0 ]; then
        # keep CN_JSON: the PERF stage's bench --config fleet reuses it
        # (APEX_TPU_CANARY_ARTIFACT) instead of a second drill
        rm -f "$CN_SPANS" /tmp/_t1_canary_timeline.json
        echo "TIER1-CANARY: PASS"
    else
        echo "TIER1-CANARY: FAIL (rc=$canary_rc; artifacts at" \
            "$CN_JSON $CN_SPANS)"
    fi
fi

perf_rc=0
if [ "${T1_SKIP_PERF:-0}" != "1" ]; then
    # 1a. the flatline catch: r03 vs r05 sat at 43 TFLOP/s — the gate
    #     MUST exit non-zero on these committed artifacts
    if python tools/bench_diff.py BENCH_all_r05.json \
        --baseline BENCH_all_r03.json --fail-on-flat \
        >/dev/null 2>>"$LOG"; then
        echo "TIER1-PERF: bench_diff failed to catch the committed" \
            "r03->r05 flash flatline" | tee -a "$LOG"
        perf_rc=1
    fi
    # 1b. ...and no false positive from the plain regression gate
    if [ "$perf_rc" -eq 0 ]; then
        python tools/bench_diff.py BENCH_all_r05.json \
            --baseline BENCH_all_r03.json --fail-on-regression \
            2>&1 | tail -n 2 | tee -a "$LOG"
        perf_rc=${PIPESTATUS[0]}
    fi
    # 2. short CPU bench configs + schema gate vs the committed golden
    #    (smoke + serve append into ONE file: the golden carries both
    #    metric sets, so --require-same-metrics needs both runs)
    if [ "$perf_rc" -eq 0 ]; then
        PERF_OUT="$(mktemp /tmp/_t1_perf.XXXXXX.jsonl)"
        timeout -k 10 300 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
            python bench.py --config smoke --metrics-out "$PERF_OUT" \
            2>&1 | tail -n 2 | tee -a "$LOG"
        perf_rc=${PIPESTATUS[0]}
        if [ "$perf_rc" -eq 0 ]; then
            # the serve config's serve_chaos_* rows reuse the
            # SERVE-CHAOS stage's evidence artifact (one storm per CI
            # pass); with the stage skipped or failed the bench runs
            # its own drill
            SC_REUSE=""
            if [ "${T1_SKIP_SERVECHAOS:-0}" != "1" ] \
                && [ "$servechaos_rc" -eq 0 ] && [ -s "${SC_JSON:-}" ]; then
                SC_REUSE="$SC_JSON"
            fi
            timeout -k 10 300 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
                APEX_TPU_SERVE_CHAOS_ARTIFACT="$SC_REUSE" \
                python bench.py --config serve --metrics-out "$PERF_OUT" \
                2>&1 | tail -n 2 | tee -a "$LOG"
            perf_rc=${PIPESTATUS[0]}
            [ -n "$SC_REUSE" ] && rm -f "$SC_REUSE"
        fi
        # the trainer's honest multi-device rows (ISSUE 12): built on
        # the MOCKED 8-device mesh with --lint, so the golden stream
        # carries dp/tp >= 2 shapes the schema gate REQUIRES (a
        # degenerate train3d row is a schema failure, not an exclusion)
        if [ "$perf_rc" -eq 0 ]; then
            timeout -k 10 300 env JAX_PLATFORMS=cpu \
                XLA_FLAGS="--xla_force_host_platform_device_count=8" \
                python bench.py --config train3d --lint \
                --metrics-out "$PERF_OUT" \
                2>&1 | tail -n 2 | tee -a "$LOG"
            perf_rc=${PIPESTATUS[0]}
        fi
        # the goodput acceptance rows (ISSUE 13): the chaos-storm
        # drill's numbers ride the same golden/schema stream, so storm
        # goodput / zero-stall / bit-exact-resume can never go flat or
        # vanish silently.  The GOODPUT stage (which runs first) hands
        # its evidence artifact over so this pass emits rows from the
        # ONE drill already run; with the stage skipped or failed the
        # bench falls back to running the drill itself.
        if [ "$perf_rc" -eq 0 ]; then
            GP_REUSE=""
            if [ "${T1_SKIP_GOODPUT:-0}" != "1" ] \
                && [ "$goodput_rc" -eq 0 ] && [ -s "${GP_JSON:-}" ]; then
                GP_REUSE="$GP_JSON"
            fi
            timeout -k 10 420 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
                APEX_TPU_GOODPUT_ARTIFACT="$GP_REUSE" \
                python bench.py --config goodput --metrics-out "$PERF_OUT" \
                2>&1 | tail -n 2 | tee -a "$LOG"
            perf_rc=${PIPESTATUS[0]}
            [ -n "$GP_REUSE" ] && rm -f "$GP_REUSE"
        fi
        # the fleet acceptance rows (ISSUE 16): the control-plane
        # storm's numbers ride the same golden/schema stream, so fleet
        # goodput / zero-loss deploys / p99 inflation can never go
        # flat or vanish silently.  The FLEET stage (which runs first)
        # hands its evidence artifact over so this pass emits rows
        # from the ONE storm already run; with the stage skipped or
        # failed the bench falls back to running the drill itself.
        if [ "$perf_rc" -eq 0 ]; then
            FL_REUSE=""
            if [ "${T1_SKIP_FLEET:-0}" != "1" ] \
                && [ "$fleet_rc" -eq 0 ] && [ -s "${FL_JSON:-}" ]; then
                FL_REUSE="$FL_JSON"
            fi
            # ...and the CANARY stage's artifact rides the same config
            # (fleet_canary_detect_ticks / fleet_canary_false_positive)
            CN_REUSE=""
            if [ "${T1_SKIP_CANARY:-0}" != "1" ] \
                && [ "$canary_rc" -eq 0 ] && [ -s "${CN_JSON:-}" ]; then
                CN_REUSE="$CN_JSON"
            fi
            timeout -k 10 600 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
                APEX_TPU_FLEET_ARTIFACT="$FL_REUSE" \
                APEX_TPU_CANARY_ARTIFACT="$CN_REUSE" \
                python bench.py --config fleet --metrics-out "$PERF_OUT" \
                2>&1 | tail -n 3 | tee -a "$LOG"
            perf_rc=${PIPESTATUS[0]}
            [ -n "$FL_REUSE" ] && rm -f "$FL_REUSE"
            [ -n "$CN_REUSE" ] && rm -f "$CN_REUSE"
        fi
        if [ "$perf_rc" -eq 0 ]; then
            python tools/bench_diff.py "$PERF_OUT" \
                --baseline tools/bench_golden_cpu.jsonl \
                --check-schema --require-same-metrics \
                2>&1 | tail -n 2 | tee -a "$LOG"
            perf_rc=${PIPESTATUS[0]}
        fi
        if [ "$perf_rc" -eq 0 ]; then
            rm -f "$PERF_OUT"
        else
            echo "TIER1-PERF: smoke/schema gate failed (lines kept at" \
                "$PERF_OUT)" | tee -a "$LOG"
        fi
    fi
    # 3. the ISSUE 6 acceptance line: attribution fractions
    if [ "$perf_rc" -eq 0 ]; then
        SP_JSON="$(mktemp /tmp/_t1_stepprof.XXXXXX.json)"
        timeout -k 10 420 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
            python tools/step_profile.py --target resilient --steps 5 \
            --json "$SP_JSON" 2>&1 | tail -n 4 | tee -a "$LOG"
        perf_rc=${PIPESTATUS[0]}
        if [ "$perf_rc" -eq 0 ]; then
            python - "$SP_JSON" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
p = json.load(open(sys.argv[1]))
assert abs(p["fraction_sum"] - 1.0) <= 0.02, p["fraction_sum"]
assert set(p["fractions"]) == {"compute", "collective", "host_stall"}
assert p["device"]["platform"] == "cpu", p["device"]
assert p["mfu"] == p["roofline"] == "not measured", (p["mfu"], p["roofline"])
print(f"step_profile OK: fractions sum={p['fraction_sum']:.3f} "
      f"(source={p['source']}); roofline and MFU not measured on "
      f"{p['device']['kind']}")
PYEOF
            perf_rc=${PIPESTATUS[0]}
        fi
        if [ "$perf_rc" -eq 0 ]; then
            rm -f "$SP_JSON"
        else
            echo "TIER1-PERF: step_profile acceptance failed (json at" \
                "$SP_JSON)" | tee -a "$LOG"
        fi
    fi
    if [ "$perf_rc" -eq 0 ]; then
        echo "TIER1-PERF: PASS"
    else
        echo "TIER1-PERF: FAIL (rc=$perf_rc)"
    fi
fi

serve_rc=0
if [ "${T1_SKIP_SERVE:-0}" != "1" ]; then
    SV_OUT="$(mktemp /tmp/_t1_serve.XXXXXX.jsonl)"
    SV_DIR="$(mktemp -d /tmp/_t1_serve_demo.XXXXXX)"
    # train -> checkpoint -> restore (bit-exact assert inside) -> serve
    timeout -k 10 420 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
        python examples/simple/serve/serve_gpt.py \
        --dir "$SV_DIR" --train-steps 8 --requests 5 \
        --metrics-out "$SV_OUT" 2>&1 | tail -n 5 | tee -a "$LOG"
    serve_rc=${PIPESTATUS[0]}
    if [ "$serve_rc" -eq 0 ]; then
        python - "$SV_OUT" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
recs = [json.loads(l) for l in open(sys.argv[1]) if l.strip()]
assert recs, "serving metrics JSONL is empty"
metrics = {r["metric"] for r in recs}
for need in ("serve/ttft_ms", "serve/tokens_per_s", "serve/queue_depth",
             "serve/batch_fill", "serve/page_occupancy"):
    assert need in metrics, f"missing metric {need}; have {sorted(metrics)}"
def last(name):
    return [r for r in recs if r["metric"] == name][-1]["value"]
ttft = last("serve/ttft_ms")
tps = last("serve/tokens_per_s")
assert isinstance(ttft, (int, float)) and ttft > 0, f"ttft={ttft!r}"
assert isinstance(tps, (int, float)) and tps > 0, f"tokens/s={tps!r}"
assert last("serve/completed") == 5, last("serve/completed")
print(f"serving JSONL OK: {len(recs)} records, ttft={ttft:.2f}ms "
      f"tokens/s={tps:.1f}, 5/5 completed")
PYEOF
        serve_rc=${PIPESTATUS[0]}
    fi
    if [ "$serve_rc" -eq 0 ]; then
        # the decode/prefill AOT programs must lint clean (exit 1 on
        # any ERROR — the ISSUE 7 acceptance gate)
        SERVE_LINT_JSON="${T1_SERVE_LINT_JSON:-/tmp/_t1_serve_lint.json}"
        timeout -k 10 300 env JAX_PLATFORMS=cpu \
            python tools/graph_lint.py --target serve \
            --json "$SERVE_LINT_JSON" 2>&1 | tail -n 2 | tee -a "$LOG"
        serve_rc=${PIPESTATUS[0]}
    fi
    # span-accounting gate (ISSUE 8): a closed-loop serve_bench run
    # records every request's span chain; tools/timeline.py must prove
    # the record complete (one terminal per admitted request, TTFT
    # components summing to the measured TTFT within 1ms, zero ring
    # drops) and emit a Perfetto-loadable trace.
    if [ "$serve_rc" -eq 0 ]; then
        SB_JSON="$(mktemp /tmp/_t1_servebench.XXXXXX.json)"
        SB_SPANS="$(mktemp /tmp/_t1_spans.XXXXXX.json)"
        SB_TRACE="$(mktemp /tmp/_t1_trace.XXXXXX.json)"
        timeout -k 10 420 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
            python tools/serve_bench.py --requests 8 \
            --json "$SB_JSON" --spans "$SB_SPANS" \
            2>&1 | tail -n 4 | tee -a "$LOG"
        serve_rc=${PIPESTATUS[0]}
        if [ "$serve_rc" -eq 0 ]; then
            timeout -k 10 120 env JAX_PLATFORMS=cpu \
                python tools/timeline.py --spans "$SB_SPANS" \
                --out "$SB_TRACE" --json 2>&1 | tee -a "$LOG"
            serve_rc=${PIPESTATUS[0]}
        fi
        if [ "$serve_rc" -eq 0 ]; then
            python - "$SB_JSON" "$SB_SPANS" "$SB_TRACE" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
art = json.load(open(sys.argv[1]))
spans = json.load(open(sys.argv[2]))
trace = json.load(open(sys.argv[3]))
# the wall-clock anchor satellite: every artifact from the process
# carries the same monotonic->epoch offset
for name, d in (("serve_bench", art), ("spans", spans)):
    a = d.get("anchor") or {}
    assert {"monotonic", "epoch"} <= set(a), f"{name} missing anchor: {a}"
assert art["anchor"]["epoch"] == spans["anchor"]["epoch"], "anchor drift"
# TTFT attribution p95s appear BOTH in the artifact and on the registry
ta = art["load"]["ttft_attribution"]
for comp in ("queue_wait", "cached_prefill", "prefill", "contention"):
    assert "p95" in ta[f"{comp}_ms"], ta
    key = f"serve/ttft_{comp}_ms_p95"
    assert key in art["registry"], f"missing {key} on the registry board"
# per-reason shed breakdown sums to the shed total, both surfaces
req = art["load"]["requests"]
assert sum(req["shed_reasons"].values()) == req["shed"], req
reg = art["registry"]
assert sum(
    v for k, v in reg.items()
    if k.startswith("serve/shed_")
) == reg["serve/shed"], reg
# the merged trace is Chrome-trace-event JSON with real events
assert trace["traceEvents"], "empty Perfetto trace"
assert any(e.get("ph") == "X" for e in trace["traceEvents"])
print(f"span gate OK: {req['completed']}/{req['offered']} requests, "
      f"{len(trace['traceEvents'])} trace events, queue-wait p95="
      f"{ta['queue_wait_ms']['p95']:.2f}ms")
PYEOF
            serve_rc=${PIPESTATUS[0]}
        fi
        if [ "$serve_rc" -eq 0 ]; then
            rm -f "$SB_JSON" "$SB_SPANS" "$SB_TRACE"
        else
            echo "TIER1-SERVE: span-accounting gate failed (artifacts" \
                "at $SB_JSON $SB_SPANS $SB_TRACE)" | tee -a "$LOG"
        fi
    fi
    # prefix-cache gate (ISSUE 17): an 85%-shared Poisson workload with
    # the content-addressed prefix cache armed must prove the headline
    # win — cache-hit p50 TTFT <= 0.3x cold-miss p50 at equal load,
    # >= 50% of prefill FLOPs saved — AND prove it did not buy speed
    # with correctness: the replay harness re-decodes every completed
    # request on a cache-disabled scheduler and demands bit-identical
    # token streams, and every leak_check (one per drained step plus
    # final drain) must have passed with the cache holding pages.
    if [ "$serve_rc" -eq 0 ]; then
        PFX_JSON="$(mktemp /tmp/_t1_prefix.XXXXXX.json)"
        timeout -k 10 420 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
            python tools/serve_bench.py --requests 20 --rate 40 \
            --prompt-mix 72 80 --output-mix 4 8 --pages 120 \
            --prefix-cache --shared-prefix-tokens 64 --shared-frac 0.85 \
            --chunk-tokens 16 --json "$PFX_JSON" \
            2>&1 | tail -n 4 | tee -a "$LOG"
        serve_rc=${PIPESTATUS[0]}
        if [ "$serve_rc" -eq 0 ]; then
            python - "$PFX_JSON" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
art = json.load(open(sys.argv[1]))
pfx = art["load"]["prefix"]
assert pfx["hit_requests"] > 0, pfx
assert pfx["miss_requests"] > 0, pfx
hit = pfx["hit_ttft_ms"]["p50"]
miss = pfx["miss_ttft_ms"]["p50"]
ratio = hit / miss
assert ratio <= 0.3, (
    f"hit p50 {hit:.2f}ms vs miss p50 {miss:.2f}ms -> ratio "
    f"{ratio:.3f} > 0.3: prefix cache is not paying for itself")
saved = pfx["prefill_flops_saved_pct"]
assert saved >= 50.0, f"prefill FLOPs saved {saved:.1f}% < 50%"
rp = pfx["replay"]
assert rp["bit_identical"], (
    f"cached decode diverged from uncached reference: {rp}")
assert pfx["leak_checks_run"] > 0, pfx
assert pfx["cache"]["commits"] > 0, pfx
print(f"prefix gate OK: {pfx['hit_requests']} hit / "
      f"{pfx['miss_requests']} miss, hit p50 {hit:.2f}ms vs miss "
      f"{miss:.2f}ms (ratio {ratio:.3f}), FLOPs saved {saved:.1f}%, "
      f"replay bit-identical over {rp['replayed']} requests, "
      f"{pfx['leak_checks_run']} leak checks clean")
PYEOF
            serve_rc=${PIPESTATUS[0]}
        fi
        if [ "$serve_rc" -eq 0 ]; then
            rm -f "$PFX_JSON"
        else
            echo "TIER1-SERVE: prefix-cache gate failed (artifact at" \
                "$PFX_JSON)" | tee -a "$LOG"
        fi
    fi
    # speculative-decode gate (ISSUE 18): a friendly (self-draft)
    # speculative run at k=4 must prove the headline — >= 1.5 emitted
    # tokens per decode step — WITHOUT buying speed with correctness:
    # the replay harness re-decodes every completed request on a
    # speculation-free engine and demands bit-identical streams, and a
    # planted serve.draft fault storm (raise at two draft rounds) must
    # leave every stream intact and the pool leak-clean with
    # draft-namespace pages in flight.
    if [ "$serve_rc" -eq 0 ]; then
        SPEC_JSON="$(mktemp /tmp/_t1_spec.XXXXXX.json)"
        timeout -k 10 420 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
            APEX_TPU_CHAOS="serve.draft:raise@1,3" \
            python tools/serve_bench.py --requests 10 \
            --output-mix 8 12 --speculate 4 --json "$SPEC_JSON" \
            2>&1 | tail -n 5 | tee -a "$LOG"
        serve_rc=${PIPESTATUS[0]}
        if [ "$serve_rc" -eq 0 ]; then
            python - "$SPEC_JSON" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
art = json.load(open(sys.argv[1]))
sp = art["load"]["spec"]
assert sp["k"] == 4 and sp["rounds"] > 0, sp
rp = sp["replay"]
assert rp["bit_identical"], (
    f"speculative decode diverged from plain reference: {rp}")
# self-draft greedy acceptance is exact except in the wake of the
# planted faults (a plain-fallback round leaves the draft KV one
# token behind until the next round's first column heals it)
assert sp["accept_rate"] >= 0.8, (
    f"self-draft greedy acceptance {sp['accept_rate']} < 0.8")
tps = sp["tokens_per_step"]
assert tps >= 1.5, f"spec tokens/decode-step {tps:.2f} < 1.5 at k=4"
assert sp["draft_faults"] >= 1, (
    f"planted serve.draft storm never landed: {sp}")
assert sp["leak_checks_run"] > 0, sp
print(f"spec gate OK: {sp['rounds']:.0f} rounds, accept rate "
      f"{100 * sp['accept_rate']:.1f}%, {tps:.2f} tokens/step, "
      f"{sp['draft_faults']:.0f} draft faults absorbed, replay "
      f"bit-identical over {rp['replayed']} requests, "
      f"{sp['leak_checks_run']} leak checks clean")
PYEOF
            serve_rc=${PIPESTATUS[0]}
        fi
        if [ "$serve_rc" -eq 0 ]; then
            rm -f "$SPEC_JSON"
        else
            echo "TIER1-SERVE: speculative-decode gate failed (artifact" \
                "at $SPEC_JSON)" | tee -a "$LOG"
        fi
    fi
    if [ "$serve_rc" -eq 0 ]; then
        rm -rf "$SV_DIR"
        rm -f "$SV_OUT"
        echo "TIER1-SERVE: PASS"
    else
        echo "TIER1-SERVE: FAIL (rc=$serve_rc; metrics at $SV_OUT," \
            "demo dir $SV_DIR)"
    fi
fi

ops_rc=0
if [ "${T1_SKIP_OPS:-0}" != "1" ]; then
    OPS_JSON="$(mktemp /tmp/_t1_ops.XXXXXX.json)"
    OPS_SPANS="$(mktemp /tmp/_t1_ops_spans.XXXXXX.json)"
    OPS_TRACE="$(mktemp /tmp/_t1_ops_trace.XXXXXX.json)"
    # the planted deadline storm: a 1ms TTFT objective every admission
    # blows, judged by an in-process-scaled (0.1s, 0.4s, 2x) window
    # pair — the fast-burn alert must fire DURING the run and land on
    # the span timeline beside the requests that blew the budget.  The
    # run must SPAN the long window's min_coverage (half of it) or the
    # tracker honestly reports no-evidence and nothing fires: 32
    # requests keep the run comfortably past 0.2s on a fast box.
    timeout -k 10 420 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
        python tools/serve_bench.py --requests 32 --rate 300 \
        --output-mix 8 16 24 \
        --slo-ttft-ms 1 --slo-burn-short 0.1 --slo-burn-long 0.4 \
        --ops-port 0 --spans "$OPS_SPANS" --json "$OPS_JSON" \
        2>&1 | tail -n 6 | tee -a "$LOG"
    ops_rc=${PIPESTATUS[0]}
    if [ "$ops_rc" -eq 0 ]; then
        timeout -k 10 120 env JAX_PLATFORMS=cpu \
            python tools/timeline.py --spans "$OPS_SPANS" \
            --out "$OPS_TRACE" 2>&1 | tee -a "$LOG"
        ops_rc=${PIPESTATUS[0]}
    fi
    if [ "$ops_rc" -eq 0 ]; then
        python - "$OPS_JSON" "$OPS_SPANS" "$OPS_TRACE" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
sys.path.insert(0, ".")
from apex_tpu.observability.ometrics import parse_exposition
art = json.load(open(sys.argv[1]))
spans = json.load(open(sys.argv[2]))
trace = json.load(open(sys.argv[3]))
# 1. the endpoint served OpenMetrics-valid text, live under load AND
#    after the final registry drain
ops = art["ops"]
assert ops["mid_scrape"] and ops["mid_scrape"]["ok"], ops["mid_scrape"]
assert ops["scrape"]["content_type"].startswith(
    "application/openmetrics-text"), ops["scrape"]["content_type"]
fams = parse_exposition(ops["scrape"]["text"])  # raises on violations
for need in ("apex_tpu_serve_ttft_ms", "apex_tpu_serve_ttft_hist_ms",
             "apex_tpu_serve_queue_depth", "apex_tpu_serve_completed",
             "apex_tpu_memstats_device0_peak_bytes_in_use"):
    assert need in fams, f"scrape missing {need}; have {len(fams)} families"
# the scrape's values EQUAL the artifact registry section (the scrape
# ran after the drain — zero-cadence staleness)
reg = art["registry"]
for key, fam in (("serve/completed", "apex_tpu_serve_completed"),
                 ("serve/shed", "apex_tpu_serve_shed"),
                 ("serve/queue_depth", "apex_tpu_serve_queue_depth"),
                 ("serve/ttft_ms", "apex_tpu_serve_ttft_ms")):
    assert fams[fam]["value"] == reg[key], (key, fams[fam]["value"], reg[key])
# 2. the storm fired the fast-burn SLO alert, critically, and it is ON
#    the timeline with the request spans
slo = art["slo"]
assert slo["alerts_fired"] >= 1, slo
ttft_alerts = [e for e in slo["events"] if e["rule"] == "slo_ttft"]
assert ttft_alerts and ttft_alerts[0]["severity"] == "critical", slo["events"]
health = [e for e in spans["spans"]
          if e.get("track") == "health" and e["name"] == "health/slo_ttft"]
assert health, "SLO alert missing from the span dump's health track"
assert any(e.get("name") == "health/slo_ttft"
           for e in trace["traceEvents"]), "alert not in the merged trace"
# 3. the honest fake-provider memstats run reconciles cleanly
mem = art["memstats"]
assert mem["provider"] == "fake", mem["provider"]  # CPU tier
assert mem["findings"] == [], mem["findings"]
assert mem["watermark_samples"] > 0
assert len(mem["static_peaks"]) >= 2, mem["static_peaks"]
print(f"OPS gate OK: {len(fams)} families served, "
      f"{slo['alerts_fired']} SLO alert(s) on the timeline, memstats "
      f"reconciled over {len(mem['static_peaks'])} static programs")
PYEOF
        ops_rc=${PIPESTATUS[0]}
    fi
    if [ "$ops_rc" -eq 0 ]; then
        # the planted static-vs-live drift: a fake watermark at 2x the
        # static peak MUST come back as a finding naming the program
        OPS_DRIFT="$(mktemp /tmp/_t1_ops_drift.XXXXXX.json)"
        timeout -k 10 300 env JAX_PLATFORMS=cpu XLA_FLAGS="" \
            python tools/serve_bench.py --requests 3 \
            --memstats-fake-scale 2.0 --json "$OPS_DRIFT" \
            2>&1 | tail -n 2 | tee -a "$LOG"
        ops_rc=${PIPESTATUS[0]}
        if [ "$ops_rc" -eq 0 ]; then
            python - "$OPS_DRIFT" <<'PYEOF' 2>&1 | tee -a "$LOG"
import json, sys
mem = json.load(open(sys.argv[1]))["memstats"]
assert mem["findings"], "planted 2x drift was NOT flagged"
f = mem["findings"][0]
assert f["direction"] == "static-under-predicts", f
assert f["program"], f
assert abs(f["ratio"] - 2.0) < 0.05, f
print(f"planted drift flagged OK: {f['program']} at {f['ratio']:.2f}x")
PYEOF
            ops_rc=${PIPESTATUS[0]}
        fi
        if [ "$ops_rc" -eq 0 ]; then
            rm -f "$OPS_DRIFT"
        else
            echo "TIER1-OPS: planted-drift check failed (artifact at" \
                "$OPS_DRIFT)" | tee -a "$LOG"
        fi
    fi
    if [ "$ops_rc" -eq 0 ]; then
        rm -f "$OPS_JSON" "$OPS_SPANS" "$OPS_TRACE"
        echo "TIER1-OPS: PASS"
    else
        echo "TIER1-OPS: FAIL (rc=$ops_rc; artifacts at $OPS_JSON" \
            "$OPS_SPANS $OPS_TRACE)"
    fi
fi

if [ "$rc" -eq 0 ] && [ "$comm_rc" -eq 0 ] && [ "$obs_rc" -eq 0 ] \
    && [ "$flight_rc" -eq 0 ] && [ "$lint_rc" -eq 0 ] \
    && [ "$train_rc" -eq 0 ] && [ "$perf_rc" -eq 0 ] \
    && [ "$serve_rc" -eq 0 ] && [ "$ops_rc" -eq 0 ] \
    && [ "$goodput_rc" -eq 0 ] && [ "$servechaos_rc" -eq 0 ] \
    && [ "$fleet_rc" -eq 0 ] && [ "$canary_rc" -eq 0 ]; then
    echo "TIER1: PASS"
else
    echo "TIER1: FAIL (pytest rc=$rc, comm rc=$comm_rc, obs rc=$obs_rc, flight rc=$flight_rc, lint rc=$lint_rc, train rc=$train_rc, perf rc=$perf_rc, serve rc=$serve_rc, ops rc=$ops_rc, goodput rc=$goodput_rc, serve-chaos rc=$servechaos_rc, fleet rc=$fleet_rc, canary rc=$canary_rc)"
fi
[ "$rc" -ne 0 ] && exit "$rc"
[ "$comm_rc" -ne 0 ] && exit "$comm_rc"
[ "$obs_rc" -ne 0 ] && exit "$obs_rc"
[ "$flight_rc" -ne 0 ] && exit "$flight_rc"
[ "$lint_rc" -ne 0 ] && exit "$lint_rc"
[ "$train_rc" -ne 0 ] && exit "$train_rc"
[ "$perf_rc" -ne 0 ] && exit "$perf_rc"
[ "$serve_rc" -ne 0 ] && exit "$serve_rc"
[ "$ops_rc" -ne 0 ] && exit "$ops_rc"
[ "$goodput_rc" -ne 0 ] && exit "$goodput_rc"
[ "$servechaos_rc" -ne 0 ] && exit "$servechaos_rc"
[ "$fleet_rc" -ne 0 ] && exit "$fleet_rc"
exit "$canary_rc"
