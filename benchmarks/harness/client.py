"""The plain HTTP a run speaks to the broker and the debug endpoints.
Times are `time.monotonic()`."""
from __future__ import annotations

import json
import time
import urllib.error
import urllib.request
from typing import Optional


def http_json(url: str, body: Optional[dict] = None, timeout: float = 30.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data,
        headers={"Content-Type": "application/json"} if data else {})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def with_options(pql: str, **opts) -> str:
    extra = ", ".join(f"{k}={v}" for k, v in opts.items())
    if pql.rstrip().endswith(")") and " OPTION(" in pql:
        return pql.rstrip()[:-1] + ", " + extra + ")"
    return f"{pql} OPTION({extra})"


def complete(body: dict) -> Optional[str]:
    """None where the response is whole, else what is missing: the
    guarantee is every queried server answered and nothing was flagged."""
    if body.get("exceptions"):
        return f"exceptions: {str(body['exceptions'])[:300]}"
    if body.get("partialResponse"):
        return "partialResponse"
    if not (body.get("numServersResponded") ==
            body.get("numServersQueried") and
            body.get("numServersQueried", 0) >= 1):
        return (f"{body.get('numServersResponded')} of "
                f"{body.get('numServersQueried')} servers responded")
    return None


def post_query(url: str, pql: str, timeout: float) -> dict:
    """One request -> {t_send, t_recv, body | error}; never raises."""
    rec = {"t_send": time.monotonic()}
    try:
        req = urllib.request.Request(
            url, data=json.dumps({"pql": pql}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw = resp.read()
        rec["t_recv"] = time.monotonic()
        rec["body"] = json.loads(raw)
        rec["error"] = complete(rec["body"])
    except (urllib.error.URLError, OSError, ValueError) as e:
        rec.setdefault("t_recv", time.monotonic())
        rec["error"] = f"{type(e).__name__}: {e}"
    return rec
