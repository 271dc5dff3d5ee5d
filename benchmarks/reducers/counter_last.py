"""A gauge as it stood after the window closed."""


def reduce(ctx, spec):
    return ctx["counters"]["after"].get(spec["params"]["key"])
