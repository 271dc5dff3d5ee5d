"""Closed loop: one thread a client; each sends its next request when
the last one has been answered (or has failed), until the window
closes. A request in flight at the close is waited for: its latency
counts, its answer does not count towards the rate. No think time.

A driver is found by the name a traffic file gives under `driver` and
has one entry, `run_window(url, traffic, seconds, traced_share,
timeout)` -> {t_open, t_close, requests}; an open-loop driver is one
more file here.
"""
from __future__ import annotations

import threading
import time
from typing import List

from harness.client import post_query, with_options


def run_window(url: str, traffic, seconds: float, traced_share: int,
               timeout: float) -> dict:
    """Drive every client for `seconds`; -> {t_open, t_close, requests}.
    `traced_share` n > 0 puts OPTION(trace=true) on every n-th request
    of a client (the traced run), 0 on none."""
    records: List[List[dict]] = [[] for _ in range(traffic.clients)]
    streams = [traffic.client_stream(k) for k in range(traffic.clients)]
    gate = threading.Event()
    times = {}

    def client(k: int) -> None:
        gate.wait()
        for seq, request in enumerate(streams[k]):
            if time.monotonic() >= times["close"]:
                return
            traced = traced_share > 0 and seq % traced_share == 0
            pql = with_options(request.pql, trace="true") if traced \
                else request.pql
            rec = post_query(url, pql, timeout)
            rec.update(client=k, seq=seq, shape=request.shape.name,
                       literals=request.literals, traced=traced)
            records[k].append(rec)

    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(traffic.clients)]
    for t in threads:
        t.start()
    times["open"] = time.monotonic()
    times["close"] = times["open"] + seconds
    gate.set()
    for t in threads:
        t.join(timeout=seconds + 2 * timeout + 60)
    if any(t.is_alive() for t in threads):
        raise RuntimeError("a client did not finish after the window")
    return {"t_open": times["open"], "t_close": times["close"],
            "requests": [r for rs in records for r in rs]}
