"""ms per step of the step program's device ops whose output is a whole
table: ops under ``<step_name>/`` whose result type is f32 with
``n_features`` rows (``f32[<rows>]``, ``f32[<rows>,<cols>]``; ``f32_<rows>``
followed by ``_`` or the end once the name is made of ``[A-Za-z0-9_.]``,
as the ledger writes it). These are the row updates' scatters into ``w``
and ``v`` (on the mesh into the ``v`` shard) and the copies of the
tables into the step's outputs. Control-flow ops (a ``conditional``,
``while`` or ``call`` whose result holds a table) are left out: their
time is that of the ops inside them, counted on their own."""

import re

_WRAPPERS = ("conditional", "while", "call")


def read(r):
    p = r["trace"]["programs"].get(r["step_name"])
    if not p or p["count"] <= 0:
        return None
    table = re.compile(r"f32_%d(_|$)" % int(r["cfg"]["n_features"]))
    prefix = r["step_name"] + "/"
    seconds = 0.0
    for name, s in r["trace"]["device_ops"]:
        if not name.startswith(prefix):
            continue
        short = name[len(prefix):]
        if short.split()[-1:] and short.split()[-1] in _WRAPPERS:
            continue
        if table.search(re.sub(r"[^A-Za-z0-9_.]", "_", short)):
            seconds += s
    return 1e3 * seconds / p["count"]
