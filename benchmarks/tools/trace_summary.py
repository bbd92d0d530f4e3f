#!/usr/bin/env python3
"""Print what one looks at by hand in a trace before trusting the reduction:
planes, lines, programs, host annotations and the operations that took most
time. `python3 benchmarks/tools/trace_summary.py <dir or .xplane.pb>`."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import xplane  # noqa: E402


def main(path: str) -> None:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines[:40])
    print(json.dumps(xplane.Trace(path).summary(), indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
