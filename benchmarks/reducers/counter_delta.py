"""Growth of the `keys` counters over the window, summed. Nothing where
the program keeps none of them (a parent commit from before the counter
existed): the metric is then left out of the line."""


def reduce(ctx, spec):
    counters, keys = ctx["counters"], spec["params"]["keys"]
    if not any(k in counters["after"] for k in keys):
        return None
    return sum(counters["delta"].get(k, 0) for k in keys)
