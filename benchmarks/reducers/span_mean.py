"""Mean over the traced queries of (sum of the `add` spans minus sum of
the `subtract` spans), in milliseconds. Spans are `obs/tracing.py`'s,
read from each traced response's `traceTree`; a span name counts every
time it occurs in a tree (once a segment, for the per-segment spans)."""


def span_sums(node, names, out):
    if node.get("name") in names:
        out[node["name"]] = out.get(node["name"], 0.0) + \
            float(node.get("ms", 0.0))
    for child in node.get("children") or ():
        span_sums(child, names, out)
    return out


def reduce(ctx, spec):
    add, sub = spec["params"]["add"], spec["params"].get("subtract", [])
    values = []
    for r in ctx["requests"]:
        tree = (r.get("body") or {}).get("traceTree")
        if not r.get("traced") or r.get("error") or not tree:
            continue
        sums = span_sums(tree, set(add) | set(sub), {})
        if not any(n in sums for n in add):
            continue
        values.append(sum(sums.get(n, 0.0) for n in add) -
                      sum(sums.get(n, 0.0) for n in sub))
    return sum(values) / len(values) if values else None
