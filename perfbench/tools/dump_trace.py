#!/usr/bin/env python3
"""Print the structure of an ``.xplane.pb``: planes, lines, event counts, and
the first events of each line with every stat. Look at a trace with this
before writing a reader against it."""

from __future__ import annotations

import sys

from jax.profiler import ProfileData


def dump(path: str, per_line: int = 6, out=sys.stdout) -> None:
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            names = {}
            for ev in events:
                names[ev.name] = names.get(ev.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:25]
            print(f"    names: {top}", file=out)
            for ev in events[:per_line]:
                stats = {k: (str(v)[:300]) for k, v in ev.stats}
                print(f"    EVENT {ev.name!r} start_ns={ev.start_ns} "
                      f"dur_ns={ev.duration_ns} stats={stats}", file=out)


if __name__ == "__main__":
    dump(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 6)
