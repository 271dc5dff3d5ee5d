"""One general closed-loop generator, driven by a traffic file.

`benchmarks/traffic/<name>.json` gives the clients, the shape family and
how a client picks. Every client walks shuffled decks of the family's
shapes (each block of len(shapes) requests holds every shape once, so
every seed offers the same work in another order) and takes each
shape's literals from a seeded affine walk over the shape's domain:
slot i -> (a*i + b) mod N with gcd(a, N) = 1 visits N distinct tuples,
client k of C takes slots k, k+C, ..., and the warm-up's untraced
bursts take slots from the top, which the window never reaches. So no
(shape, literals) pair is sent twice in a window and no answer can come
from a result cache. A shape whose domain is used up
drops out of a client's decks; the run reports how often.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from typing import Dict, Iterator, List

import numpy as np

from . import shapes as shapes_mod


@dataclass
class Request:
    shape: shapes_mod.Shape
    literals: Dict[str, object]
    pql: str


class Traffic:
    def __init__(self, spec: dict, shapes: List[shapes_mod.Shape],
                 seed: int):
        self.spec = spec
        self.shapes = shapes
        self.seed = seed
        self.clients = int(spec["clients"])
        self.by_name = {s.name: s for s in shapes}
        self._walk = {}
        for i, s in enumerate(shapes):
            rng = np.random.default_rng([seed, 2000 + i])
            n = s.domain_size
            a = int(rng.integers(1, max(n, 2)))
            while math.gcd(a, n) != 1:
                a = a % n + 1
            self._walk[s.name] = (a, int(rng.integers(0, n)), n)
        # only the warm-up's bursts are not traced, so only their
        # literals could be met again in a result cache: keep them
        bursts = spec["warm"].get("bursts") or {"shapes": [], "rounds": []}
        self.reserved = {s.name: sum(bursts["rounds"])
                         if s.name in bursts["shapes"] else 0
                         for s in shapes}
        self.exhausted = 0

    def _request(self, shape, slot: int) -> Request:
        a, b, n = self._walk[shape.name]
        lits = shape.literals((a * slot + b) % n)
        return Request(shape, lits, shape.pql(lits))

    def client_stream(self, k: int) -> Iterator[Request]:
        """Client k's requests, without end (until every domain is dry)."""
        rng = np.random.default_rng([self.seed, 1000 + k])
        used = {s.name: 0 for s in self.shapes}
        while True:
            sent = 0
            for i in rng.permutation(len(self.shapes)):
                shape = self.shapes[i]
                slot = used[shape.name] * self.clients + k
                if slot >= shape.domain_size - self.reserved[shape.name]:
                    self.exhausted += 1
                    continue
                used[shape.name] += 1
                sent += 1
                yield self._request(shape, slot)
            if not sent:
                return

    def warm_round(self, i: int) -> List[Request]:
        """Warm-up round i: one request a shape, walking each domain
        from the top down. The window may meet such a literal tuple
        again; a result cache cannot, since warm-up rounds are traced
        and a traced request bypasses the caches both ways."""
        return [self._request(
            s, (s.domain_size - 1 - self.reserved[s.name] - i)
            % s.domain_size) for s in self.shapes]

    def warm_bursts(self) -> List[List[Request]]:
        """Rounds of same-shape requests to send at once, so that the
        batched programs the window can meet are compiled before it."""
        spec = self.spec["warm"].get("bursts")
        if not spec:
            return []
        out = []
        for name in spec["shapes"]:
            shape = self.by_name[name]
            slot = shape.domain_size - 1
            for size in spec["rounds"]:
                out.append([self._request(shape, slot - j)
                            for j in range(size)])
                slot -= size
        return out


def load(bench_dir: str, name: str) -> dict:
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as fh:
        return json.load(fh)
