"""An end-to-end metric, as the harness itself took it."""


def reduce(ctx, spec):
    return ctx["end_to_end"].get(spec["name"])
