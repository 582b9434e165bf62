#!/usr/bin/env python3
"""Look at a trace by hand: its planes, their lines, and the names that
take most time on each line.

    python3 benchmark/trace/inspect.py <trace directory or .xplane.pb> [out.json [start_s length_s floor_us]]

With a second argument a part of the trace is also written out as JSON
(the form `reduce.reduce` takes): that is how the recorded trace under
benchmark/tests/data/ was made.
"""

from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.trace import reduce as R  # noqa: E402


def main(argv: list) -> int:
    path = argv[0]
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    planes = R.load(path)
    print(f"{path}: {os.path.getsize(path) / 1e6:.1f} MB")
    for plane in planes:
        print(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            events = line["events"]
            if not events:
                continue
            total: dict = {}
            for name, _, duration in events:
                total[name] = total.get(name, 0.0) + duration
            top = sorted(total.items(), key=lambda item: -item[1])[:8]
            span = (max(s + d for _, s, d in events)
                    - min(s for _, s, _ in events)) / 1e9
            print(f"  LINE {line['name']!r}: {len(events)} events over "
                  f"{span:.3f} s")
            for name, duration in top:
                print(f"      {duration / 1e9:9.4f} s  {name[:100]}")
    if len(argv) > 1:
        # a recording for the tests: `length` seconds from `start` seconds
        # into the traced span, device operations under `floor`
        # microseconds left out, names cut to 64 characters
        start, length, floor = (float(v) for v in (argv[2:5] + [
            "0.5", "0.25", "20"][len(argv[2:5]):]))
        traced = next(e for plane in planes for line in plane["lines"]
                      for e in line["events"] if e[0] == R.SPAN_TRACED)
        low = traced[1] + start * 1e9
        high = low + length * 1e9
        cut = []
        for plane in planes:
            device = plane["name"].startswith(R.DEVICE_PREFIX)
            lines = []
            for line in plane["lines"]:
                keep = [[name[:64], max(s, low), min(s + d, high) - max(s, low)]
                        for name, s, d in line["events"]
                        if s + d > low and s < high
                        and (name.startswith("bench.") if not device else
                             line["name"] == R.MODULE_LINE or
                             (line["name"] == R.OPS_LINE and d >= floor * 1e3))]
                if keep:
                    lines.append({"name": line["name"], "events": keep})
            if lines:
                cut.append({"name": plane["name"], "lines": lines})
        with open(argv[1], "w") as f:
            json.dump(cut, f, separators=(",", ":"))
        print(f"wrote {argv[1]}: {os.path.getsize(argv[1]) / 1e3:.0f} kB")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
