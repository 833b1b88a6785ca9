"""ms per step of the row update: device time of the step program's ops
whose result has a dimension equal to ``n_features`` (the tables' rows:
the kernel that writes them or a scatter into them, and copies of a
table), whatever the update's form. Control-flow ops (a ``conditional``,
``while`` or ``call``) are left out: their time is that of the ops inside
them, counted on their own. An op whose result type the trace does not
give (``<module>/<op>`` with no type) is counted as well, as one that
may write a table. Nothing when the step did not run."""

import re

_WRAPPERS = ("conditional", "while", "call")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def update_seconds(r):
    """Device seconds of the step's table-writing ops over the window."""
    n = int(r["cfg"]["n_features"])
    prefix = r["step_name"] + "/"
    seconds = 0.0
    for name, s in r["trace"]["device_ops"]:
        if not name.startswith(prefix):
            continue
        short = name[len(prefix):]
        if short.split()[-1:] and short.split()[-1] in _WRAPPERS:
            continue
        shapes = _SHAPE.findall(short)
        if not shapes or any(str(n) in dims.split(",") for dims in shapes):
            seconds += s
    return seconds


def read(r):
    p = r["trace"]["programs"].get(r["step_name"])
    if not p or p["count"] <= 0:
        return None
    return 1e3 * update_seconds(r) / p["count"]
