"""Share of the device's program time, in %, spent in programs whose
name starts with `params.prefix`: the events of the line `XLA Modules`
(one event an executed program, named `jit_<function>(<fingerprint>)`)
on the device planes of the traced slice. Nothing where the trace holds
no such line (the CPU rehearsal)."""

MODULE_LINE = "XLA Modules"
DEVICE_PLANE_PREFIX = "/device:"


def module_events(events):
    """[(start_ns, end_ns, name)] of every executed program, by start."""
    out = [(start, start + dur, name)
           for plane, line, name, start, dur in events
           if plane.startswith(DEVICE_PLANE_PREFIX) and line == MODULE_LINE]
    out.sort()
    return out


def reduce(ctx, spec):
    trace = ctx["trace"]
    if not trace:
        return None
    prefix = spec["params"]["prefix"]
    total = named = 0
    for start, end, name in module_events(trace["events"]):
        total += end - start
        if name.startswith(prefix):
            named += end - start
    return 100.0 * named / total if total > 0 else None
