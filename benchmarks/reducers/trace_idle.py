"""Share of the traced slice in which no op ran on the device, in %."""


def reduce(ctx, spec):
    trace = ctx["trace"]
    if not trace or trace["busy_s"] <= 0 or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
