"""The server child: the program's StartServer entry, wrapped.

Run in place of `python -m pinot_tpu.tools.admin` for `server:*`
processes. Only the process that holds the chip can trace it or read
its memory peak, and the program has no hook for either, so this
wrapper adds both and otherwise hands its arguments unchanged to
`pinot_tpu.tools.admin.main`:

- with BENCH_TRACE_DIR set, a daemon thread watches that directory for
  `trace.start` / `trace.stop`, brackets `jax.profiler` between them and
  writes `trace.done`; unset, the thread is never started;
- with BENCH_STATS_FILE set, the backend's memory statistics are written
  there when the server has stopped.
"""
import json
import os
import sys
import threading
import time


def _watch(trace_dir: str) -> None:
    import jax
    start, stop, done = (os.path.join(trace_dir, n) for n in
                         ("trace.start", "trace.stop", "trace.done"))
    while not os.path.exists(start):
        time.sleep(0.02)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # device ops and XLA's own host
    options.host_tracer_level = 1        # events only: small and cheap
    jax.profiler.start_trace(os.path.join(trace_dir, "profile"),
                             profiler_options=options)
    with open(os.path.join(trace_dir, "trace.started"), "w") as fh:
        fh.write(str(time.time()))
    while not os.path.exists(stop):
        time.sleep(0.02)
    t = time.time()
    jax.profiler.stop_trace()
    with open(done, "w") as fh:
        json.dump({"stopped": t, "written": time.time()}, fh)


def _write_stats(path: str) -> None:
    import jax
    stats = jax.local_devices()[0].memory_stats() or {}
    with open(path, "w") as fh:
        json.dump({k: v for k, v in stats.items()
                   if isinstance(v, (int, float))}, fh)


def main(argv) -> int:
    from pinot_tpu.tools import admin
    trace_dir = os.environ.get("BENCH_TRACE_DIR")
    if trace_dir:
        threading.Thread(target=_watch, args=(trace_dir,),
                         daemon=True).start()
    try:
        return admin.main(argv)
    finally:
        stats_file = os.environ.get("BENCH_STATS_FILE")
        if stats_file:
            _write_stats(stats_file)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
