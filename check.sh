#!/bin/sh
# One-shot verification of the whole framework:
#   sh check.sh
# Runs the Python test suite (forced 8-device virtual CPU mesh via
# tests/conftest.py), the ASan/UBSan native selftest, the multi-process
# shm demo scenarios, the MPI-path syntax check, the driver entry-point
# dryrun, and the tiny-size benchmark suite. Exits nonzero on the first
# failure.
#
# The sanitized selftest also runs INSIDE the pytest suite
# (tests/test_native_selftest.py), so the C engine's ack/retransmit
# and fault-injection paths are sanitizer-clean in tier-1, not just in
# this script; the explicit leg below keeps a fast standalone entry
# point and covers environments that skip pytest.
set -e
cd "$(dirname "$0")"

echo "== rlo-model (exhaustive protocol model checking + automaton parity) =="
# explicit-state exploration of EVERY interleaving of the small
# membership/healing/IAR configurations (n=3: one-kill-one-rejoin,
# healed split-brain, crossed stale syncs) against invariants M1-M5,
# plus the cross-engine membership automaton extracted from BOTH
# engine.py and rlo_engine.c (A1 parity, A2 extracted<->explored
# coverage) and the sim-backed mode driving the REAL engines through
# transport.sim — docs/DESIGN.md §20. Also in tier-1
# (tests/test_model.py). The timeout IS the wall budget: exhaustive
# at this scale or not at all.
timeout 10 python -m rlo_tpu.tools.rlo_model

echo "== static analyzers (merged rlo-lint+sentinel+prover+model report) =="
# all four analyzers in one process via runner.run_static: cross-engine
# conformance (docs/DESIGN.md §9), CFG/dataflow safety (§15), symbolic
# schedule/geometry proofs (§16), and the protocol model checker (§20)
# — one merged --json findings document, consumed here with a per-tool
# timing line (the timing prints on stderr; the document must parse
# and be finding-free). Each analyzer also runs inside tier-1
# (tests/test_{lint,sentinel,prover,model}.py).
static_json=$(mktemp -t rlo_static.XXXXXX)
timeout 60 python -m rlo_tpu.tools.runner --json > "$static_json"
python - "$static_json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
tools = {t["tool"]: t for t in doc["tools"]}
assert set(tools) == {"rlo-lint", "rlo-sentinel", "rlo-prover",
                      "rlo-model"}, sorted(tools)
assert doc["findings"] == [], doc["findings"]
print(" ".join(f"{n}={t['seconds']:.2f}s" for n, t in tools.items()))
EOF
rm -f "$static_json"

echo "== pytest =="
python -m pytest tests/ -q

echo "== native selftest (ASan/UBSan) =="
(cd rlo_tpu/native && make -s selftest && ./rlo_selftest)

echo "== native selftest (TSan) =="
# ThreadSanitizer variant of the full selftest (loopback chaos paths
# included). The engine model is single-threaded cooperative polling,
# so tsan.supp is expected to stay empty — a report here is a real
# race, most likely in a transport that grew threads.
(cd rlo_tpu/native && make -s tsan && \
    TSAN_OPTIONS="suppressions=$PWD/tsan.supp" ./rlo_selftest_tsan)

echo "== TCP transport under TSan (socket mesh) =="
(cd rlo_tpu/native && TSAN_OPTIONS="suppressions=$PWD/tsan.supp" \
    ./tcprun -n 8 -t 240 ./rlo_demo_tsan -m 4 -b 65536)

echo "== multi-process demo + TCP under ASan/UBSan =="
(cd rlo_tpu/native && make -s demo_asan && ./rlo_demo_asan -n 8 -m 8 && \
    ./tcprun -n 8 -t 240 ./rlo_demo_asan -m 4 -b 65536)

echo "== multi-process demo =="
(cd rlo_tpu/native && make -s demo && ./rlo_demo -n 8 -m 8)

echo "== MPI transport syntax check =="
(cd rlo_tpu/native && make -s mpicheck)

echo "== MPI transport executed (femtompi mpirun) =="
(cd rlo_tpu/native && make -s mpidemo && \
    ./femtompirun -n 8 -t 240 ./rlo_demo_mpi -m 4 -b 65536)

echo "== TCP transport executed (socket mesh) =="
(cd rlo_tpu/native && ./tcprun -n 8 -t 240 ./rlo_demo -m 4 -b 65536)

echo "== observability smoke (loopback soak -> chrome timeline) =="
# 4-rank soak with tracing + metrics on and fault injection, per-rank
# JSONL dumps merged to a Chrome trace-event file, schema validated
# (flow edges included) — docs/DESIGN.md §7
JAX_PLATFORMS=cpu python -m rlo_tpu.utils.timeline smoke

echo "== fleet telescope smoke (rlo-top --json, 8-rank sim fleet) =="
# in-band telemetry plane (docs/DESIGN.md §17): drive a seeded 8-rank
# sim fleet, converge the Tag.TELEM digests, and self-check the view
# from rank 0 — every live rank's digest present and fleet rollups
# equal to the sum of the per-rank captures (exit 1 on drift)
JAX_PLATFORMS=cpu python -m rlo_tpu.tools.rlo_top --json --ranks 8 \
    --vtime 12 > /dev/null

echo "== incident watchdog mutation fixture (canary rule must trip) =="
# a watchdog that never fires is indistinguishable from none: hand a
# healthy fleet an SLO mutated down to a threshold ordinary traffic
# crosses, and require the trip plus a complete incident bundle
# (rule + fleet view + traces) — the check.sh-sized mirror of
# tests/test_observe.py's churn-cascade leg
JAX_PLATFORMS=cpu python - <<'EOF'
import json, os, tempfile
from rlo_tpu.tools.rlo_top import run_fleet
d = tempfile.mkdtemp(prefix="rlo_incident.")
fleet = run_fleet(4, seed=0,
                  watchdog_rules=["canary: sum(sent_bcast) >= 1"],
                  incident_dir=d)
fleet.drive(8.0)
fleet.converge()
incs = [i for p in fleet.planes if p.watchdog
        for i in p.watchdog.incidents]
assert incs, "mutated canary SLO never tripped"
first = next(i for i in incs if i.bundle_dir)
names = sorted(os.listdir(first.bundle_dir))
assert "incident.json" in names and "fleet_view.json" in names, names
doc = json.load(open(os.path.join(first.bundle_dir, "incident.json")))
assert doc["name"] == "canary" and doc["value"] >= 1, doc
fleet.cleanup()
print(f"canary tripped at vtime {first.vtime:.1f}; bundle: {names}")
EOF

echo "== causal trace smoke (rlo-trace --json, seeded 8-rank fabric_kill) =="
# request-scoped causal tracing (docs/DESIGN.md §19): run the seeded
# fabric_kill failover shape with every rid sampled, reconstruct the
# span trees, and require a complete report — every traced request
# delivered and stage attribution telescoping exactly to e2e (exit 1
# on analyzer findings, 2 on tool error). The same (kind, seed) pair
# is pinned bit-for-bit across runs by tests/test_spans.py. The
# timeout IS the wall budget.
JAX_PLATFORMS=cpu timeout 10 python -m rlo_tpu.tools.rlo_trace \
    --scenario fabric_kill --seed 7 --world-size 8 --json > /dev/null

echo "== collective attribution smoke (rlo-scope --json, seeded 8-rank ring) =="
# collective data-plane observatory (docs/DESIGN.md §21): run the
# instrumented ring allreduce on the seeded sim substrate and join the
# measured Ev.STEP timings against the rlo-prover-checked cost ledger
# — step identities, per-rank send counts, and payload bytes must all
# match the ledger exactly (S1/S2) and the reduction must be right
# (S3); exit 1 on findings, 2 on tool error. The same report is
# bit-for-bit pinned per (schedule, n, seed) by tests/test_scope.py.
JAX_PLATFORMS=cpu timeout 10 python -m rlo_tpu.tools.rlo_scope \
    --schedule ring_allreduce --n 8 --seed 0 --json > /dev/null

echo "== simulator fuzz sweep (25 seeds x 13 chaos scripts) =="
# fixed-seed deterministic sweep over the partition/restart/burst-loss/
# mixed scenario scripts — exactly-once, termination, and membership
# convergence checked per run — plus the churn_weather healing shape
# (sustained churn_script kills/rejoins UNDER Gilbert burst loss with
# the default watchdog SLOs armed: any incident is a sweep violation,
# docs/DESIGN.md §18) — PLUS the serving-fabric shapes
# (fabric_kill/fabric_split/fabric_rejoin/fabric_paged and the
# weather-driven fabric_churn: sustained kill/rejoin churn from a
# seeded churn_script, docs/DESIGN.md §11/§14): exactly-once request
# completion with oracle-identical tokens, re-admission after heal,
# and placement convergence — PLUS the §22 remediation shapes
# (remedy_flap/remedy_hotspot/remedy_split: default watchdog SLOs AND
# the consensus-gated RemedyPolicy armed — the fleet must quarantine
# the flapper through IAR, throttle admissions under the hotspot,
# never dual-quarantine across a partition, and recover fully once
# the fault clears). A violation prints the seed + a replay
# recipe with the live pending-event count (docs/DESIGN.md §8). The C
# engine runs the same protocol shapes via the native loopback fault
# hooks inside pytest (tests/test_membership.py); the long 500-run
# sweep is `pytest tests/test_sim.py -m slow`.
JAX_PLATFORMS=cpu python -m rlo_tpu.transport.sim --seeds 25

echo "== engine bench smoke + perf gate (BENCH_engine.json) =="
# message-engine throughput at the committed-baseline (--quick) config,
# gated against the committed numbers: wall metrics at generous factors,
# seed-deterministic frame counts at zero tolerance — docs/DESIGN.md §10.
# Includes the round-13 native_batched leg (batched vs one-call-per-
# frame driving, ARQ+metrics+profiler on; the bench itself asserts the
# >=5x bar); the full (non-quick) run's tcp leg drives the socket mesh
# through the batched GIL-releasing pump — docs/DESIGN.md §13
fresh_engine=$(mktemp -t rlo_bench_engine.XXXXXX)
JAX_PLATFORMS=cpu python benchmarks/engine_bench.py --quick \
    --out "$fresh_engine" > /dev/null
JAX_PLATFORMS=cpu python -m rlo_tpu.tools.perf_gate \
    --baseline BENCH_engine.json --fresh "$fresh_engine" --report
rm -f "$fresh_engine"

echo "== simulator scaling curve + perf gate (BENCH_sim.json) =="
# protocol-only fast path: fan-out latency + membership convergence vs n
# up to 1024 simulated ranks, PLUS the round-14 weather curves —
# churn-rate-vs-convergence (every leg now ends converged: the §18
# healing work moved the knee past r=0.05 at n=32, pinned by the
# heal-cost counters) and ARQ-retransmit-storm-under-correlated-loss
# (docs/DESIGN.md §14, §18); virtual-time metrics gate at zero
# tolerance (same seed => identical schedule), so O(log n)
# regressions fail here
fresh_sim=$(mktemp -t rlo_bench_sim.XXXXXX)
JAX_PLATFORMS=cpu python benchmarks/sim_bench.py \
    --out "$fresh_sim" > /dev/null
JAX_PLATFORMS=cpu python -m rlo_tpu.tools.perf_gate \
    --baseline BENCH_sim.json --fresh "$fresh_sim" --report
rm -f "$fresh_sim"

echo "== serving-fabric bench + perf gate (BENCH_fabric.json) =="
# 4/8-rank fabric legs in the deterministic simulator: drain vtime,
# schedule events, fail-over requeues and fleet e2e latency are all
# seed-exact and gate at zero tolerance — a protocol change that adds
# a hop or slows fail-over fails mechanically (docs/DESIGN.md §11).
# The failover4_remedy leg pins the whole §22 remediation loop the
# same way: schedule digest, IAR decision count, executed
# quarantines, and the recovered end state (nothing quarantined,
# backpressure back at 0)
fresh_fabric=$(mktemp -t rlo_bench_fabric.XXXXXX)
JAX_PLATFORMS=cpu python benchmarks/fabric_bench.py \
    --out "$fresh_fabric" > /dev/null
JAX_PLATFORMS=cpu python -m rlo_tpu.tools.perf_gate \
    --baseline BENCH_fabric.json --fresh "$fresh_fabric" --report
rm -f "$fresh_fabric"

echo "== workload bench + perf gate (BENCH_workload.json, 10k smoke) =="
# the traffic laboratory (docs/DESIGN.md §14): trace-generator digests
# for every canned workload shape, the calendar-queue n=10,000-rank
# protocol-only fan-out AND membership-convergence datapoints (with an
# in-bench heap-oracle equivalence assertion at n=256), and the
# trace-driven fabric + DecodeServer serving legs — every metric
# seed-exact at zero tolerance. The `timeout` IS the wall-time budget
# for the 10k-rank smoke: the whole bench must finish inside it.
fresh_workload=$(mktemp -t rlo_bench_workload.XXXXXX)
JAX_PLATFORMS=cpu timeout 420 python benchmarks/workload_bench.py \
    --out "$fresh_workload" > /dev/null
JAX_PLATFORMS=cpu python -m rlo_tpu.tools.perf_gate \
    --baseline BENCH_workload.json --fresh "$fresh_workload" --report
rm -f "$fresh_workload"

echo "== serve bench arrival mix + perf gate (BENCH_serve.json) =="
# open-loop Poisson production mix on the tiny model: the scheduling
# metrics (rounds, occupancy, slot-step efficiency, e2e-in-rounds)
# are seed-deterministic and gate exact; wall tok/s is informational.
# --paged adds the paged-server leg (same trace, occupancy/efficiency
# must strictly beat dense — asserted in the bench AND gated exact)
# and the prefix-heavy radix-reuse leg (docs/DESIGN.md §12)
fresh_serve=$(mktemp -t rlo_bench_serve.XXXXXX)
JAX_PLATFORMS=cpu python benchmarks/serve_bench.py --tiny \
    --arrivals poisson --paged --out "$fresh_serve"
JAX_PLATFORMS=cpu python -m rlo_tpu.tools.perf_gate \
    --baseline BENCH_serve.json --fresh "$fresh_serve" --report
rm -f "$fresh_serve"

echo "== collective bench + perf gate (BENCH_collective.json) =="
# collective data-plane legs (docs/DESIGN.md §21): instrumented sim
# runs pin step-event counts, measured-fleet bytes (== the ledger's
# account), substrate message counts, virtual drain times, and ledger
# digests at zero tolerance; the jax wall-clock GB/s-vs-psum legs are
# informational on CPU and become the ROADMAP item 2 bandwidth bar on
# a real slice. The full (non-quick) run is required: the baseline's
# wall legs must stay structurally present.
fresh_coll=$(mktemp -t rlo_bench_coll.XXXXXX)
JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    python benchmarks/collective_bench.py --out "$fresh_coll"
JAX_PLATFORMS=cpu python -m rlo_tpu.tools.perf_gate \
    --baseline BENCH_collective.json --fresh "$fresh_coll" --report
rm -f "$fresh_coll"

echo "== manual-ring validation (8 virtual devices) =="
JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/ring_validation.py --mb 1

echo "== driver dryrun (8 virtual devices) =="
JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python __graft_entry__.py 8

echo "== benchmark suite (tiny, 8 virtual devices) =="
JAX_PLATFORMS=cpu \
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python benchmarks/suite.py --tiny

echo "ALL CHECKS PASSED"
