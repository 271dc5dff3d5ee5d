"""`.xplane.pb` -> a plain event list, in a process of its own.

    python trace_extract.py <profile dir> <out.json>

Reads the newest `*.xplane.pb` under the directory with
`jax.profiler.ProfileData` (no backend is initialised; the caller sets
JAX_PLATFORMS=cpu all the same) and writes
{"planes": [{"name", "lines": [{"name", "events"}]}],
 "events": [[plane, line, name, start_ns, duration_ns], ...]}
with the events of the device planes only; `planes` lists every plane
and line with its event count, for reading a trace by hand.
"""
import glob
import json
import os
import sys

DEVICE_PLANE_PREFIX = "/device:"


def extract(profile_dir: str) -> dict:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(files[-1])
    planes, events = [], []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            n = 0
            for ev in line.events:
                n += 1
                if plane.name.startswith(DEVICE_PLANE_PREFIX):
                    events.append([plane.name, line.name, ev.name,
                                   int(ev.start_ns), int(ev.duration_ns)])
            lines.append({"name": line.name, "events": n})
        planes.append({"name": plane.name, "lines": lines})
    return {"file_bytes": os.path.getsize(files[-1]), "planes": planes,
            "events": events}


if __name__ == "__main__":
    with open(sys.argv[2], "w") as out:
        json.dump(extract(sys.argv[1]), out)
