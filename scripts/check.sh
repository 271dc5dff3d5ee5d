#!/usr/bin/env bash
# CI gate: tier-1 tests, then tpulint against the committed baseline.
# Either failing fails the build.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== tier-1 pytest =="
env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider

echo "== chaos (broker fault tolerance) =="
# dedicated gate: the fault-injection suite must stay green and fast
# even if a future tier-1 filter stops collecting it implicitly
env JAX_PLATFORMS=cpu python -m pytest tests/test_chaos.py tests/test_retry.py \
    -q -p no:cacheprovider

echo "== crash recovery (durability plane) =="
# kill-and-restart gates: WAL/snapshot recovery, torn tails, seeded
# crash points, cold-start reloads, integrity quarantine + repair ...
env JAX_PLATFORMS=cpu python -m pytest tests/test_crash_recovery.py \
    -q -p no:cacheprovider
# ... plus a scripted kill-restart of the distributed quickstart that
# must converge (zero re-downloads) within a bounded window
env JAX_PLATFORMS=cpu python scripts/crash_restart_smoke.py

echo "== upsert (mutable-scenario durability) =="
# primary-key dedup crash gates: kill -9 mid upsert stream at each
# seeded crash point, restart, exact-count + latest-value convergence
# with host-vs-device masked-result parity ...
env JAX_PLATFORMS=cpu python -m pytest tests/test_upsert.py \
    -q -p no:cacheprovider
# ... plus a scripted kill-restart that must converge with ZERO topic
# re-reads before the key-map snapshot offset
env JAX_PLATFORMS=cpu python scripts/upsert_smoke.py

echo "== self-healing (membership churn + controller failover) =="
# continuous two-table load (OFFLINE + REALTIME upserts) while the
# harness kill -9s the consuming server, then the lead controller, then
# SIGTERM-drains a server: replication must repair, consumption resume
# with exact-count/latest-value convergence, the standby serve commits
# within ~one lease period, and the drain cost zero query errors
env JAX_PLATFORMS=cpu python -m pytest tests/test_selfheal.py \
    -q -p no:cacheprovider
env JAX_PLATFORMS=cpu python scripts/selfheal_smoke.py

echo "== compaction soak (background maintenance plane) =="
# two-phase soak at 2x upsert churn: WITHOUT maintenance the key map
# and masked-dead rows grow monotonically; WITH the minion plane
# (deadness-driven compaction swaps + TTL retention with delayed
# delete + upsert key GC) scan p99, committed docs and
# upsertKeyMapSize stay flat — while a kill -9 of the minion
# (compact.staged) and of the swap driver (compact.pre_swap) both
# recover exactly from the durable intent records, with COUNT(*) ==
# key-map size at every checkpoint; artifact: COMPACT_r09.json
env JAX_PLATFORMS=cpu python scripts/compaction_smoke.py

echo "== tenant isolation (ingress control) =="
# two-tenant overload gate: an aggressor flooding at 10x its per-tenant
# token-bucket quota must be throttled with typed 429s while the victim
# tenant sharing the table keeps its unloaded steady-state p99 (within
# 1.5x + a CI-noise floor); quota/admission/result-cache unit suites
# run in tier-1 above — this drives the stack end to end
env JAX_PLATFORMS=cpu python scripts/tenant_isolation_smoke.py

echo "== vector search (similarity over mutable embeddings) =="
# embedded cluster with a primary-key upsert table carrying a VECTOR
# column: filtered VECTOR_SIMILARITY top-k must match the independent
# numpy oracle bit-exactly, an upsert published mid-run must rank FIRST
# on the next converged query, and the superseded row must never rank
env JAX_PLATFORMS=cpu python scripts/vector_smoke.py

echo "== join smoke (multi-stage query engine) =="
# SSB-style dim × fact through the full stage plane: broadcast +
# co-partitioned joins exact vs the numpy oracle, stage-1 blocks
# fetched over the TCP exchange byte-identically, window invariants +
# determinism, DISTINCTCOUNTHLL register-identical to the host sketch,
# host/device/sharded join parity, and a REALTIME upsert fact table
# whose join tracks mid-run upserts (superseded rows never join)
env JAX_PLATFORMS=cpu python scripts/join_smoke.py

echo "== qps smoke (serving plane) =="
# one short target-QPS rung over the real TCP mux: catches serving-plane
# regressions (per-connection serialization, serde blow-ups) in seconds
env JAX_PLATFORMS=cpu python scripts/qps_smoke.py

echo "== obs smoke (observability plane) =="
# /metrics must serve valid Prometheus exposition on broker + servers +
# controller, and a trace=true query must return a non-empty merged
# trace tree with per-server subtrees
env JAX_PLATFORMS=cpu python scripts/obs_smoke.py

echo "== residency smoke (tiered memory pressure) =="
# a working set ~3x the device budget must serve with graceful
# degradation: every answer bit-equal to the unbounded twin run, the
# HBM ledger never above budget at checkpoints, the full
# device->host->disk ladder exercised (promotions/demotions/cold hits
# all nonzero), and a bounded p99 penalty — never a cliff or a wrong
# answer
env JAX_PLATFORMS=cpu python scripts/residency_smoke.py

echo "== batch smoke (cross-query dispatch coalescing) =="
# a concurrent same-plan-shape mix must coalesce (batchOccupancy > 1)
# and answer bit-identically to a batchWindowMs=0 sequential twin —
# catches member-mixing fan-backs and literals leaking into the
# shared kernel spec in seconds
env JAX_PLATFORMS=cpu python scripts/batch_smoke.py

echo "== chip smoke, CPU rehearsal (the served path as OS processes) =="
# the command the chip tool runs (python chip_smoke.py: controller +
# broker + ONE server process, SSB without cubes through REST upload and
# HTTP /query, every answer against the numpy reference, a coalesced
# burst, the kernel compile sweep) rehearsed at a tiny size on the CPU
# backend so it cannot rot between chip runs. Its output is marked
# REHEARSAL: what it proves about a chip is nothing — there is no chip
# step in this gate.
python chip_smoke.py --rehearse-cpu

echo "== production soak (short mode: one cluster, every subsystem) =="
# 120s scaled-down soak of the FULL production shape: multi-process HA
# cluster (standalone store + lead/standby controller + servers +
# broker + minion) serving the weighted mix (SSB + joins + windows +
# VECTOR_SIMILARITY + 2-tenant quotas) while realtime upserts churn,
# with a deterministic chaos schedule firing one kill -9 of a serving
# server and one lead-controller failover mid-run. Gates: ZERO
# unflagged errors (every BrokerResponse exception carries a
# machine-readable errorCode), per-class p99 in bounds, recoveries
# inside deadlines, leak gauges flat. Full 30+ min run commits
# SOAK_r15.json; this short gate reuses the identical harness.
env PINOT_TPU_SOAK_SECONDS="${PINOT_TPU_SOAK_SECONDS:-120}" \
    SOAK_ARTIFACT="${SOAK_ARTIFACT:-/tmp/soak_ci.json}" \
    python scripts/prod_soak.py

echo "== tpulint (deep + protocol tiers) =="
# --deep adds the below-the-AST gates on top of the AST families:
# every registered kernel is traced with jax.make_jaxpr across the
# shape-bucket grid (no host callbacks, no 64-bit avals in 32-bit
# mode, stable retrace) and the serde wire surface must round-trip
# against the committed wire-schema.json. --protocol adds the
# crash-protocol gates: staged-write durability ordering over the
# durable writers, crash-point coverage (every durable mutation
# splittable, every point armed by a test), the metrics exposition
# contract, an exhaustive crash-interleaving model check of the
# extracted lease/rebalance/takeover/upsert-seal/drain/compact-swap
# transition systems against the written ROBUSTNESS.md invariants
# (state counts logged; hitting --max-states is a finding, never
# silent), and a drift gate against the committed protocol-model.json.
# On failure the CLI prints a findings-diff summary (rule id,
# file:line, fix-or-suppress guidance) — and for invariant violations,
# the counterexample trace. --lifecycle adds the resource-lifecycle
# tier (device uploads routed through the residency ledger, query-path
# caches structurally bounded), --sarif exports every finding for CI
# annotation, and lint.sh fails the gate if the whole four-tier run
# exceeds its wall-time budget (default 30s).
exec "$(dirname "$0")/lint.sh" --lifecycle --deep --protocol \
    --sarif lint.sarif
