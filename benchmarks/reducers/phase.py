"""Seconds one set-up phase took, by the harness's clock."""


def reduce(ctx, spec):
    return ctx["phases"].get(spec["params"]["phase"])
