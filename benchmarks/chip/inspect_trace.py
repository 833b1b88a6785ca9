"""Print a profiler trace's planes, lines and first events (for reading a
trace by hand before trusting ``trace_reduce``)."""

import sys

from jax.profiler import ProfileData

sys.path.insert(0, __file__.rsplit("/", 1)[0])
from trace_reduce import find_xplane  # noqa: E402

pd = ProfileData.from_file(find_xplane(sys.argv[1]))
n = int(sys.argv[2]) if len(sys.argv) > 2 else 4
for plane in pd.planes:
    lines = list(plane.lines)
    print(f"PLANE {plane.name!r}: {len(lines)} lines")
    for line in lines:
        evs = list(line.events)
        print(f"  LINE {line.name!r}: {len(evs)} events")
        for e in evs[:n]:
            print(f"    {e.name!r} start {e.start_ns} dur {e.duration_ns} "
                  f"stats {dict(e.stats)}")
