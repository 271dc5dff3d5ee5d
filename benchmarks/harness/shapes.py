"""Query shapes as data: one description makes the PQL and the reference.

A shape file (`benchmarks/shapes/<family>.json`) holds, for each query,
its predicates with placeholders, each placeholder's domain, the
group-by columns, the aggregates and the query's own TOP / OPTION, and
`top` is a number or the name of a parameter (a page size that holds
every group either way, so answers do not depend on it, while a result
cache sees another query); the family's `compared` names, for each aggregated column, the number
of `correct` that its sums feed. From
that one description this module renders the PQL string the broker gets
and computes the numpy reference answer (masks over the generated id
lanes, `bincount` sums in float64). Nothing of the program is imported.

Parameter kinds (a domain is an ordered list of options):
  int        lo..hi                         -> one number
  pool       every value of `column`'s pool -> one value
  band       [a, a+w-1] inside lo..hi, for each w of `widths`
  pool_band  `width` neighbours in `column`'s sorted pool -> [first, last]
  subset     `size` values out of `values` or `column`'s pool, all from
             one block of `block` neighbours where given, first and last
             `gap` = [least, most] positions apart where given -> list
  run        `size` neighbours of `values` or `column`'s pool -> list
"""
from __future__ import annotations

import itertools
import json
import os
from typing import Dict, List

import numpy as np


def param_domain(spec: dict, pools) -> list:
    kind = spec["kind"]
    if kind == "int":
        return list(range(spec["lo"], spec["hi"] + 1))
    if kind == "pool":
        return [_plain(v) for v in pools[spec["column"]]]
    if kind == "band":
        return [[a, a + w - 1] for w in spec["widths"]
                for a in range(spec["lo"], spec["hi"] - w + 2)]
    if kind == "pool_band":
        vals, w = pools[spec["column"]], spec["width"]
        return [[_plain(vals[i]), _plain(vals[i + w - 1])]
                for i in range(len(vals) - w + 1)]
    if kind == "subset":
        vals = [_plain(v) for v in (spec.get("values") or
                                    pools[spec["column"]])]
        block = spec.get("block") or len(vals)
        lo, hi = spec.get("gap") or (0, len(vals))
        return [[vals[i] for i in c] for b in range(0, len(vals), block)
                for c in itertools.combinations(
                    range(b, min(b + block, len(vals))), spec["size"])
                if lo <= c[-1] - c[0] <= hi]
    if kind == "run":
        vals = [_plain(v) for v in (spec.get("values") or
                                    pools[spec["column"]])]
        return [vals[i:i + spec["size"]]
                for i in range(len(vals) - spec["size"] + 1)]
    raise ValueError(f"unknown parameter kind {kind!r}")


def _plain(v):
    return int(v) if isinstance(v, (int, np.integer)) else str(v)


def _lit(v) -> str:
    return str(v) if isinstance(v, int) else f"'{v}'"


class Shape:
    """One query shape over one table's pools."""

    def __init__(self, spec: dict, table: str, pools, numbers=None):
        self.spec = spec
        # aggregate -> the compared number its sums feed
        self.numbers = {a: (numbers or {})[a] for a in spec["aggregates"]}
        self.name = spec["name"]
        self.table = table
        self.pools = pools
        self.param_names = list(spec["params"])
        self.domains = [param_domain(spec["params"][p], pools)
                        for p in self.param_names]
        self.domain_size = int(np.prod([len(d) for d in self.domains]))
        self.columns = sorted({w["col"] for w in spec["where"]} |
                              set(spec["group_by"]) | set(spec["aggregates"]))

    # -- literals ----------------------------------------------------------
    def literals(self, index: int) -> Dict[str, object]:
        """Mixed-radix decode of `index` in [0, domain_size)."""
        out = {}
        for name, dom in zip(reversed(self.param_names),
                             reversed(self.domains)):
            index, r = divmod(index, len(dom))
            out[name] = dom[r]
        return {p: out[p] for p in self.param_names}

    def index_of(self, literals: Dict[str, object]) -> int:
        index = 0
        for name, dom in zip(self.param_names, self.domains):
            index = index * len(dom) + dom.index(literals[name])
        return index

    # -- the PQL the broker gets ------------------------------------------
    def pql(self, literals: Dict[str, object]) -> str:
        preds = []
        for w in self.spec["where"]:
            v, col = literals[w["param"]], w["col"]
            if w["op"] == "eq":
                preds.append(f"{col} = {_lit(v)}")
            elif w["op"] == "lt":
                preds.append(f"{col} < {_lit(v)}")
            elif w["op"] == "between":
                preds.append(f"{col} BETWEEN {_lit(v[0])} AND {_lit(v[1])}")
            elif w["op"] == "in":
                preds.append(f"{col} IN ({', '.join(map(_lit, v))})")
            else:
                raise ValueError(f"unknown predicate {w['op']!r}")
        aggs = ", ".join(f"SUM({a})" for a in self.spec["aggregates"])
        q = f"SELECT {aggs} FROM {self.table} WHERE {' AND '.join(preds)}"
        if self.spec["group_by"]:
            q += f" GROUP BY {', '.join(self.spec['group_by'])}"
        if self.spec.get("top"):
            top = self.spec["top"]
            q += f" TOP {literals[top] if isinstance(top, str) else top}"
        if self.spec.get("options"):
            q += " OPTION(" + ", ".join(
                f"{k}={v}" for k, v in self.spec["options"].items()) + ")"
        return q

    # -- the reference answer ---------------------------------------------
    def _mask(self, ids, literals) -> np.ndarray:
        mask = None
        for w in self.spec["where"]:
            pool, lane, v = self.pools[w["col"]], ids[w["col"]], \
                literals[w["param"]]
            if pool.dtype == object:
                pool = pool.astype(str)

            def left(x):
                return int(np.searchsorted(pool, x, side="left"))

            def right(x):
                return int(np.searchsorted(pool, x, side="right"))
            if w["op"] == "eq":
                m = (lane >= left(v)) & (lane < right(v))
            elif w["op"] == "lt":
                m = lane < left(v)
            elif w["op"] == "between":
                m = (lane >= left(v[0])) & (lane < right(v[1]))
            else:
                hit = np.zeros(len(pool) + 1, bool)
                for x in v:
                    hit[left(x):right(x)] = True
                m = hit[lane]
            mask = m if mask is None else mask & m
        return mask

    def reference(self, literals, table, lower=None):
        """Scalar shapes -> (sums,) ; group shapes -> {key strings:
        (sums...)}, over every segment, in float64. `lower` maps the
        values summed to what the control sums in their place; the
        default is the values themselves. Sums of integers under 2**53
        are exact in float64."""
        aggs, gcols = self.spec["aggregates"], self.spec["group_by"]
        cards = [len(self.pools[c]) for c in gcols]
        n_groups = int(np.prod(cards)) if gcols else 1
        sums = np.zeros((len(aggs), n_groups))
        seen = np.zeros(n_groups, np.int64)
        for ids, values in table.segments:
            mask = self._mask(ids, literals)
            key = np.zeros(int(mask.sum()), np.int64)
            for c, card in zip(gcols, cards):
                key = key * card + ids[c][mask]
            seen += np.bincount(key, minlength=n_groups)
            for ai, a in enumerate(aggs):
                if a in values:
                    vals = values[a][mask].astype(np.float64)
                else:
                    vals = self.pools[a].astype(np.float64)[ids[a][mask]]
                if lower is not None:
                    vals = lower(vals)
                sums[ai] += np.bincount(key, weights=vals,
                                        minlength=n_groups)
        if not gcols:
            return tuple(float(s[0]) for s in sums)
        out = {}
        for g in np.nonzero(seen)[0]:
            rem, parts = int(g), []
            for c, card in zip(reversed(gcols), reversed(cards)):
                parts.append(str(self.pools[c][rem % card]))
                rem //= card
            out[tuple(reversed(parts))] = tuple(float(s[g]) for s in sums)
        return out


def load_family(bench_dir: str, family: str, pools) -> List[Shape]:
    with open(os.path.join(bench_dir, "shapes", f"{family}.json")) as fh:
        doc = json.load(fh)
    return [Shape(s, doc["table"], pools, doc["compared"])
            for s in doc["shapes"]]
