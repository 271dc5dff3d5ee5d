"""Sum of the `num` counters' growth over the window, over the sum of
the `den` counters' growth, times `scale`. Nothing where nothing was
counted."""


def reduce(ctx, spec):
    delta, p = ctx["counters"]["delta"], spec["params"]
    den = sum(delta.get(k, 0) for k in p["den"])
    if den <= 0:
        return None
    return p.get("scale", 1) * sum(delta.get(k, 0) for k in p["num"]) / den
