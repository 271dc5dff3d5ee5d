"""tpulint CLI: `python -m pinot_tpu.analysis [paths...]`.

Exits nonzero on findings NOT covered by the committed baseline (or on
stale baseline entries with --strict-baseline, which CI uses so the
grandfather list only ever shrinks). Run from the repo root so finding
keys match the baseline.

`--deep` additionally runs the global deep tier: jaxpr-level kernel
contracts over the registered kernel surface and the wire-schema gate
against the committed `wire-schema.json` (regenerate the latter
INTENTIONALLY with `--write-wire-schema`).

`--lifecycle` runs the resource-lifecycle tier per file: `device-ledger`
(every device upload on the serving path must route through
obs/residency.py) and `cache-bound` (every query-path cache must carry a
structural bound).

`--protocol` runs the protocol tier: durability-ordering and
crash-coverage over the durable writers, the metrics exposition
contract, and the exhaustive crash-interleaving model checker over the
extracted lease/rebalance/takeover/upsert-seal/drain transition systems
(state budget via `--max-states`; the extracted systems are committed
as `protocol-model.json`, regenerated INTENTIONALLY with
`--write-protocol-model`). `--sarif out.sarif` exports every finding —
new, grandfathered, and suppressed — as SARIF 2.1.0 for CI annotation.
"""
from __future__ import annotations

import argparse
import os
import sys

from pinot_tpu.analysis import core, runner

DEFAULT_BASELINE = "tpulint.baseline.json"

#: per-rule remediation guidance for the failure summary — the diff a
#: CI user sees should say what to DO, not just what fired
FIX_HINTS = {
    "host-sync": "batch into one jax.device_get per dispatch",
    "retrace": "hoist jit out of loops; pass hashable statics",
    "dtype-drift": "keep 64-bit math host-side (compat.wide_i64 for "
                   "genuine 64-bit lanes)",
    "concurrency": "guard both write paths with one lock, or make one "
                   "path the sole writer",
    "api-compat": "use the spelling the installed jax resolves",
    "lock-order": "impose one global acquisition order or collapse "
                  "the locks",
    "lock-blocking": "move the blocking call outside the lock "
                     "(snapshot under the lock, work outside)",
    "async-blocking": "await the async form, or offload with "
                      "loop.run_in_executor",
    "cross-loop": "create_task from coroutines; "
                  "run_coroutine_threadsafe from other threads",
    "kernel-contract": "fix the kernel (or its contract_cases entry) "
                       "until the jaxpr is callback-free, 32-bit clean "
                       "and retrace-stable",
    "wire-schema": "restore the field, or regenerate wire-schema.json "
                   "with --write-wire-schema and flag the PR as a "
                   "wire-compatibility change",
    "durability-order": "stage to .tmp, fsync per policy, os.replace, "
                        "and only then truncate/publish",
    "crash-coverage": "add a crash_points.hit at the mutation and arm "
                      "it in a kill-restart test",
    "metrics-contract": "declare the name in common/metrics.py; put "
                        "balancing gauge writes in a finally block",
    "protocol-invariants": "follow the counterexample trace; restore "
                           "the step order/guard the model extracted",
    "protocol-model": "restore the protocol shape, or regenerate "
                      "protocol-model.json with --write-protocol-model "
                      "and flag the PR as a crash-protocol change",
    "device-ledger": "route the upload through obs/residency.py "
                     "(ledgered_put / ledgered_asarray) so the bytes "
                     "are accounted",
    "cache-bound": "cap the cache (LRU/size check), key it by a "
                   "version that invalidates, or make it single-slot; "
                   "state a genuinely extrinsic bound in a suppression",
}


def _print_failure_summary(new, errors) -> None:
    """Grouped rule-id → count/guidance block printed on a failed gate."""
    by_rule = {}
    for f in new:
        by_rule.setdefault(f.rule, []).append(f)
    print("tpulint: FAILING — new findings by rule:", file=sys.stderr)
    for rule_id in sorted(by_rule):
        fs = by_rule[rule_id]
        print(f"  {rule_id} ({len(fs)}): fix → "
              f"{FIX_HINTS.get(rule_id, 'see docs/ANALYSIS.md')}",
              file=sys.stderr)
        for f in fs[:5]:
            print(f"    {f.path}:{f.line}", file=sys.stderr)
        if len(fs) > 5:
            print(f"    ... and {len(fs) - 5} more", file=sys.stderr)
    if errors:
        print(f"  plus {len(errors)} analysis error(s)", file=sys.stderr)
    print("  suppress only with a verified invariant: "
          "`# tpulint: disable=<rule> -- <why it is safe>`",
          file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m pinot_tpu.analysis",
        description="JAX-aware static analysis for pinot_tpu")
    ap.add_argument("paths", nargs="*", default=["pinot_tpu"],
                    help="files/directories to lint (repo-relative)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help=f"baseline JSON (default: {DEFAULT_BASELINE})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, grandfathered or not")
    ap.add_argument("--write-baseline", action="store_true",
                    help="regenerate the baseline from this run and exit 0")
    ap.add_argument("--strict-baseline", action="store_true",
                    help="also fail on stale baseline entries (CI mode)")
    ap.add_argument("--lifecycle", action="store_true",
                    help="also run the resource-lifecycle tier: every "
                         "device upload routed through the residency "
                         "ledger, every query-path cache structurally "
                         "bounded")
    ap.add_argument("--deep", action="store_true",
                    help="also run the deep tier: jaxpr kernel contracts "
                         "+ wire-schema gate")
    ap.add_argument("--protocol", action="store_true",
                    help="also run the protocol tier: durability order, "
                         "crash coverage, metrics contract, and the "
                         "crash-interleaving model checker")
    ap.add_argument("--max-states", type=int, default=200_000,
                    help="model-checker state budget per system "
                         "(hitting it is a FINDING, never a silent "
                         "truncation; default 200000)")
    ap.add_argument("--sarif", metavar="PATH",
                    help="also write every finding (new, grandfathered, "
                         "suppressed) as SARIF 2.1.0 to PATH")
    ap.add_argument("--write-wire-schema", action="store_true",
                    help="regenerate wire-schema.json from the live "
                         "serde surface and exit")
    ap.add_argument("--write-protocol-model", action="store_true",
                    help="regenerate protocol-model.json from the live "
                         "protocol sources and exit")
    ap.add_argument("--compile-kernels", action="store_true",
                    help="compile every registered kernel case for the "
                         "backend this process gets, execute each once, "
                         "print one JSON line and exit (takes the "
                         "device; non-zero if any case fails)")
    ap.add_argument("--rule", action="append", dest="rules", default=None,
                    help="run only this rule id (repeatable)")
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--show-suppressed", action="store_true")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rid, rule in sorted(core.all_rules().items()):
            tier = f" [{rule.tier}]" if rule.tier != "ast" else ""
            print(f"{rid:20s}{tier} {rule.description}")
        return 0

    if args.write_wire_schema:
        from pinot_tpu.analysis import contracts
        contracts.write_wire_schema()
        print(f"tpulint: wrote {contracts.WIRE_SCHEMA_FILE} — commit it "
              "and call out the wire-compatibility change in review")
        return 0

    if args.compile_kernels:
        import json

        from pinot_tpu.analysis import contracts
        out = contracts.compile_kernel_surface()
        print(json.dumps(out), flush=True)
        return 1 if out["failed"] else 0

    if args.write_protocol_model:
        from pinot_tpu.analysis import protocol
        protocol.write_protocol_model()
        print(f"tpulint: wrote {protocol.PROTOCOL_MODEL_FILE} — commit "
              "it and call out the crash-protocol change in review")
        return 0

    core.OPTIONS["max_states"] = args.max_states

    known = core.all_rules()
    if args.rules and not set(args.rules) <= set(known):
        bad = sorted(set(args.rules) - set(known))
        print(f"tpulint: unknown rule id(s) {bad}; known: "
              f"{sorted(known)}", file=sys.stderr)
        return 2
    if args.rules and not args.deep and \
            any(known[r].tier == "deep" for r in args.rules):
        # asking for a deep rule IS asking for the deep tier — without
        # this the run would silently skip the rule and report green
        args.deep = True
    if args.rules and not args.protocol and \
            any(known[r].tier == "protocol" for r in args.rules):
        args.protocol = True        # same contract for the third tier
    if args.rules and not args.lifecycle and \
            any(known[r].tier == "lifecycle" for r in args.rules):
        args.lifecycle = True       # and the fourth

    result = runner.analyze_paths(
        args.paths, rule_ids=set(args.rules) if args.rules else None,
        deep=args.deep, protocol=args.protocol,
        lifecycle=args.lifecycle)
    for err in result.errors:
        print(f"tpulint: error: {err}", file=sys.stderr)

    if args.write_baseline:
        if result.errors:
            print("tpulint: refusing to write a baseline from a run "
                  "with analysis errors", file=sys.stderr)
            return 1
        pruned, reduced = [], []
        if os.path.exists(args.baseline):
            old = core.load_baseline(args.baseline)
            fresh = core.count_keys(result.findings)
            # "pruned" = the key left the baseline entirely; a count
            # that merely shrank is still grandfathered — reporting it
            # as pruned would tell the operator a live finding is gone
            pruned = [k for k in sorted(old) if fresh.get(k, 0) == 0]
            reduced = [(k, old[k], fresh[k]) for k in sorted(old)
                       if 0 < fresh.get(k, 0) < old[k]]
        core.write_baseline(args.baseline, result.findings)
        print(f"tpulint: wrote {len(result.findings)} finding(s) to "
              f"{args.baseline}")
        if args.sarif:
            # a baseline write grandfathers everything it records, so
            # the paired SARIF reflects that: all "unchanged" (silently
            # skipping --sarif here left CI annotation steps reading a
            # missing or stale file)
            from pinot_tpu.analysis import sarif
            sarif.write_sarif(args.sarif, result.findings,
                              result.suppressed,
                              core.count_keys(result.findings))
            print(f"tpulint: wrote SARIF to {args.sarif}")
        for key in pruned:
            print(f"tpulint: pruned stale baseline entry: {key}")
        for key, was, now in reduced:
            print(f"tpulint: reduced baseline entry {was} → {now}: "
                  f"{key}")
        return 0

    baseline = {}
    if not args.no_baseline and os.path.exists(args.baseline):
        baseline = core.load_baseline(args.baseline)
    new, stale = runner.diff_baseline(result, baseline)

    if args.sarif:
        from pinot_tpu.analysis import sarif
        sarif.write_sarif(args.sarif, result.findings,
                          result.suppressed, baseline)
        print(f"tpulint: wrote SARIF to {args.sarif}")

    if args.show_suppressed:
        for f in result.suppressed:
            print(f"suppressed: {f.render()}")
    for f in new:
        print(f.render())
    for key in stale:
        print(f"tpulint: stale baseline entry (code fixed — regenerate "
              f"with --write-baseline): {key}")

    n_grandfathered = len(result.findings) - len(new)
    by_rule = ", ".join(f"{r}={n}" for r, n in
                        sorted(result.by_rule().items())) or "none"
    tier = "+".join(["fast"] +
                    (["lifecycle"] if args.lifecycle else []) +
                    (["deep"] if args.deep else []) +
                    (["protocol"] if args.protocol else []))
    print(f"tpulint[{tier}]: {len(result.findings)} finding(s) "
          f"[{by_rule}], {len(new)} new, {n_grandfathered} "
          f"grandfathered, {len(result.suppressed)} suppressed, "
          f"{len(stale)} stale baseline entr(ies)")
    if result.timings:
        shown = {"ast": "fast"}
        print("tpulint: tier wall time: " +
              " ".join(f"{shown.get(t, t)}={s:.2f}s"
                       for t, s in sorted(result.timings.items())))
    if new or result.errors or (stale and args.strict_baseline):
        if new:
            _print_failure_summary(new, result.errors)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
