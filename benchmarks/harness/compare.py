"""What decides `correct`: the timed clients' answers against the reference.

Every request of the window is held to the completeness guarantee. A
sample of the answers, drawn from the seed across all shapes, is then
compared value by value with the numpy reference (`shapes.Shape.
reference`). Each number compared has a limit of its own in the
configuration file; `verdict` returns them side by side.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List

import numpy as np

REL_FLOOR = 1.0      # a sum is nought or thousands; keeps nought finite


def canon(body: dict, n_aggs: int, grouped: bool):
    """Broker JSON -> the reference's shape: a tuple of sums, or
    {key strings: tuple of sums}."""
    aggs = body.get("aggregationResults", [])
    if not grouped:
        return tuple(0.0 if a.get("value") in (None, "null")
                     else float(a["value"]) for a in aggs)
    out: Dict[tuple, list] = {}
    for ai, a in enumerate(aggs):
        for g in a.get("groupByResult") or []:
            key = tuple(str(x) for x in g["group"])
            out.setdefault(key, [0.0] * n_aggs)[ai] = float(g["value"])
    return {k: tuple(v) for k, v in out.items()}


def rel_errs(got, exp, n_aggs: int):
    """-> (keys differ?, [widest relative gap, one an aggregate])."""
    if not isinstance(exp, dict):
        got, exp = {(): got}, {(): exp}
    if set(got) != set(exp) or any(len(v) != n_aggs for v in got.values()):
        return True, [0.0] * n_aggs
    return False, [max((abs(got[k][i] - v[i]) / max(abs(v[i]), REL_FLOOR)
                        for k, v in exp.items()), default=0.0)
                   for i in range(n_aggs)]


def sample(requests: List[dict], per_shape: int, seed: int) -> List[dict]:
    """Up to `per_shape` answered requests of every shape, drawn from the
    seed, always with the slowest answered request among them."""
    rng = np.random.default_rng([seed, 3000])
    ok = [r for r in requests if not r.get("error")]
    by_shape: Dict[str, list] = {}
    for r in sorted(ok, key=lambda r: (r["client"], r["seq"])):
        by_shape.setdefault(r["shape"], []).append(r)
    picked = []
    for name in sorted(by_shape):
        rs = by_shape[name]
        take = rng.permutation(len(rs))[:per_shape]
        picked += [rs[i] for i in sorted(take)]
    if ok:
        slowest = max(ok, key=lambda r: r["t_recv"] - r["t_send"])
        if not any(r is slowest for r in picked):
            picked.append(slowest)
    return picked


def fresh_numbers(shapes) -> dict:
    """Every number the family's aggregates feed, at nought."""
    out = {"answers_compared": 0, "keys_mismatched": 0, "worst": {},
           "sums_compared": {}}
    for shape in shapes:
        for number in shape.numbers.values():
            out.setdefault(number, 0.0)
            out["sums_compared"].setdefault(number, 0)
    return out


def fold(out: dict, shape, literals, differ: bool, errs) -> None:
    """One compared answer into the widest gaps seen so far. The shape
    file names, for each aggregate, the number its sums feed."""
    out["answers_compared"] += 1
    if differ:
        out["keys_mismatched"] += 1
        out["worst"].setdefault("keys", f"{shape.name} {literals}")
    for a, e in zip(shape.spec["aggregates"], errs):
        number = shape.numbers[a]
        out["sums_compared"][number] += 1
        if e > out[number]:
            out[number] = e
            out["worst"][number] = f"{shape.name} {literals}"


def compare_answers(picked: List[dict], shapes_by_name, table,
                    threads: int = 8) -> dict:
    """Reference over each picked request -> the widest gaps seen."""
    def one(rec):
        shape = shapes_by_name[rec["shape"]]
        n = len(shape.spec["aggregates"])
        exp = shape.reference(rec["literals"], table)
        got = canon(rec["body"], n, bool(shape.spec["group_by"]))
        return (shape, rec["literals"]) + rel_errs(got, exp, n)

    out = fresh_numbers(shapes_by_name.values())
    with ThreadPoolExecutor(max_workers=threads) as pool:
        for shape, literals, differ, errs in pool.map(one, picked):
            fold(out, shape, literals, differ, errs)
    return out


def verdict(numbers: dict, limits: dict) -> dict:
    """{name: {value, limit}} for every limited number, and whether all
    hold. A limit is the largest value that still passes."""
    compared = {n: {"value": numbers[n], "limit": spec["limit"]}
                for n, spec in limits.items()}
    ok = all(c["value"] <= c["limit"] for c in compared.values())
    return {"correct": bool(ok), "compared": compared}
