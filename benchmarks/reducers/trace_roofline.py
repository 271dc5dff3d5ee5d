"""Scan roofline share, in %: the least time the chip could take for the
bytes the slice's queries had to read (`work.lane_bytes` over the HBM
peak) over the time the device was busy in the slice. A query counts by
the share of its send-to-answer interval that lies inside the slice.
`params.shapes`, where the metric's file gives it, names the only shapes
whose queries reach the device in the metric's cells: the others are
left out of the bytes, as they are absent from the busy time."""
from harness import work


def reduce(ctx, spec):
    trace = ctx["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    t0, t1 = trace["slice"]
    cfg, total = ctx["config"], 0.0
    only = spec["params"].get("shapes")
    for r in ctx["requests"]:
        if r.get("error") or (only and r["shape"] not in only):
            continue
        span = r["t_recv"] - r["t_send"]
        inside = min(r["t_recv"], t1) - max(r["t_send"], t0)
        if span <= 0 or inside <= 0:
            continue
        total += inside / span * work.lane_bytes(
            [ctx["shapes"][r["shape"]].spec], ctx["pools"],
            ctx["value_ranges"], cfg["rows"], cfg["segments"],
            cfg.get("no_dictionary_columns") or ())
    if total <= 0:
        return None
    peak = work.peaks(ctx["bench_dir"], ctx["device_kind"])
    return 100.0 * (total / peak["hbm_bytes_per_s"]) / trace["busy_s"]
