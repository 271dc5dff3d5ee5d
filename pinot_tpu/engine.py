"""In-process query engine facade: PQL in, BrokerResponse out.

Parity: the BaseQueriesTest harness pattern
(pinot-core/src/test/.../queries/BaseQueriesTest.java:43-122) — compile →
optimize → per-segment execute → broker reduce, all in one process with no
network/cluster machinery. This is also the building block the server and
broker planes wrap.
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence

from pinot_tpu.common.response import BrokerResponse
from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
from pinot_tpu.pql.parser import compile_pql
from pinot_tpu.query.executor import ServerQueryExecutor
from pinot_tpu.query.plan import preprocess_request
from pinot_tpu.query.reduce import BrokerReduceService
from pinot_tpu.segment.loader import ImmutableSegment, ImmutableSegmentLoader


class QueryEngine:
    def __init__(self, segments: Sequence[ImmutableSegment],
                 use_device: bool = True, mesh=None):
        """`mesh`: optional jax.sharding.Mesh — when given, multi-segment
        queries run the sharded executor (segment DP with ICI combine,
        parallel/sharded.py) and fall back to sequential per-segment
        execution when segments aren't homogeneous enough
        (`ServerQueryExecutor.execute` chooses)."""
        from pinot_tpu.utils.device import configure_compile_cache
        configure_compile_cache()
        self.segments = list(segments)
        self.sharded = None
        if mesh is not None:
            from pinot_tpu.parallel.sharded import ShardedQueryExecutor
            self.sharded = ShardedQueryExecutor(mesh=mesh)
        self.executor = ServerQueryExecutor(use_device=use_device,
                                            sharded=self.sharded)
        self.optimizer = BrokerRequestOptimizer()
        self.reducer = BrokerReduceService()

    @classmethod
    def from_dirs(cls, segment_dirs: Sequence[str], **kw) -> "QueryEngine":
        return cls([ImmutableSegmentLoader.load(d) for d in segment_dirs],
                   **kw)

    def query(self, pql: str) -> BrokerResponse:
        t0 = time.perf_counter()
        request = self.optimizer.optimize(compile_pql(pql))
        # FASTHLL derived rewrite, once, while the request is still
        # private to this query: the executor plans with it and the
        # reduce names results by it (reference parity)
        request = preprocess_request(self.segments, request)
        block = self.executor.execute(request, self.segments)
        resp = self.reducer.reduce(request, [block])
        resp.time_used_ms = (time.perf_counter() - t0) * 1e3
        return resp
