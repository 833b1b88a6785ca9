"""ms per staged batch the fused producers spent fetching and parsing:
the ``dmlc:fetch`` and ``dmlc:parse`` spans of every producer thread
(``trace["spans"]``, clipped to the window), over the batches staged in
the window. Thread time: with ``nthread`` producers it may exceed the
batch interval; busy / ``nthread`` against the interval says whether
parse can set the pace. Nothing when neither span occurred."""


def read(r):
    spans = r["trace"].get("spans", {})
    busy = [spans[k]["seconds"] for k in ("dmlc:fetch", "dmlc:parse") if k in spans]
    if not r["batches"] or not busy:
        return None
    return 1e3 * sum(busy) / r["batches"]
