#!/usr/bin/env python3
"""mesh_smoke: the 13 SSB queries through ONE process driving a mesh.

    python3 scripts/mesh_smoke.py [--rows N]        # a host with >1 TPU chip
    python3 scripts/mesh_smoke.py --rehearse-cpu    # 4 virtual CPU devices

BASELINE.md config 5 ("scatter-gather combine across TPU cores") through
the documented library entry `QueryEngine(segs, mesh=make_mesh())`: the
same seeded no-cube SSB segments chip_smoke.py serves, every answer
compared with bench.py's numpy reference. It also establishes that each
query really ran the sharded executor (QueryEngine falls back to the
sequential one silently) and that every device holds its share of the
stacked lanes rather than device 0 holding them all.

Not run by the driver: `chip_smoke.py` is the one-chip proof. Fails
without a multi-device TPU unless --rehearse-cpu is given.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=0)
    ap.add_argument("--segments", type=int, default=8)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--work-dir",
                    default=os.path.join(REPO, ".chip_smoke", "mesh"))
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="REHEARSAL on 4 virtual CPU devices, tiny rows")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        print("*** CPU REHEARSAL — not a chip result ***", flush=True)
    rows = args.rows or (80_000 if args.rehearse_cpu else 100_000_000)

    import jax

    from bench import SSB_PQLS, canon_response, check, make_cpu_queries
    from pinot_tpu.engine import QueryEngine
    from pinot_tpu.obs import profiler as obs_profiler
    from pinot_tpu.obs.tracing import make_trace_context
    from pinot_tpu.parallel import make_mesh
    from pinot_tpu.tools.datagen import build_ssb_segment_dirs, ssb_pools
    from pinot_tpu.utils.device import device_report

    t0 = time.monotonic()

    def say(msg):
        print(f"mesh[{time.monotonic() - t0:7.1f}s] {msg}", flush=True)

    shutil.rmtree(args.work_dir, ignore_errors=True)
    dirs, ids, cost = build_ssb_segment_dirs(
        args.work_dir, rows, args.segments, seed=args.seed, star_tree=False)
    say(f"built {rows} rows in {args.segments} segments")
    engine = QueryEngine.from_dirs(dirs, mesh=make_mesh())
    shutil.rmtree(args.work_dir, ignore_errors=True)
    dev = device_report()
    say(f"device: {json.dumps(dev)}")
    want = "cpu" if args.rehearse_cpu else "tpu"
    if dev["platform"] != want or dev["count"] < 2:
        say(f"FAILED: need a multi-device {want} mesh")
        return 1
    cpu = make_cpu_queries(ssb_pools(args.seed), ids, cost)

    per_query = {}
    for name, pql in SSB_PQLS.items():
        profile = obs_profiler.QueryProfile("lineorder")
        t = time.monotonic()
        with obs_profiler.active(profile, make_trace_context(False)):
            resp = engine.query(pql)
        ms = (time.monotonic() - t) * 1e3
        assert not resp.exceptions, (name, resp.exceptions)
        check(name, canon_response(name, resp), cpu[name]())
        # the sequential executor attributes every segment to a path;
        # the sharded one only dispatches
        sharded = not profile.paths and profile.dispatches > 0
        per_query[name] = {"firstRunMs": round(ms, 1), "sharded": sharded,
                           "dispatches": profile.dispatches,
                           "paths": dict(profile.paths)}
        say(f"{name}: ok, sharded={sharded}, dispatches "
            f"{profile.dispatches}, first run {ms:.0f} ms")

    per_device = {}
    stack = engine.sharded.stack_for(engine.segments)
    for lane in stack._lanes.values():
        for shard in lane.addressable_shards:
            per_device[shard.device.id] = \
                per_device.get(shard.device.id, 0) + int(shard.data.nbytes)
    total = sum(per_device.values())
    shares = {d: round(b / total, 4) for d, b in sorted(per_device.items())}
    say(f"stacked lane bytes per device: {per_device} (shares {shares})")

    fallbacks = [n for n, q in per_query.items() if not q["sharded"]]
    even = len(per_device) == dev["count"] and \
        max(per_device.values()) <= 1.25 * total / dev["count"]
    ok = not fallbacks and even
    if fallbacks:
        say(f"FAILED: sequential fallback for {fallbacks}")
    if not even:
        say("FAILED: stacked lanes are not spread evenly over the mesh")
    out = {"ok": ok, "rehearsal": args.rehearse_cpu, "rows": rows,
           "segments": args.segments, "device": dev,
           "perQuery": per_query, "laneBytesPerDevice": per_device}
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "mesh_smoke.json"),
              "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps({"ok": ok, "rehearsal": args.rehearse_cpu,
                      "device": dev}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
