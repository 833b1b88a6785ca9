"""ms per staged batch the ring workers spent calling the jitted
unpacker (its host dispatch, not its device time): the
``dmlc:unpack_call`` spans of every thread (``trace["spans"]``, clipped
to the window), over the batches staged in the window. Nothing when the
span never occurred."""


def read(r):
    span = r["trace"].get("spans", {}).get("dmlc:unpack_call")
    if not r["batches"] or span is None:
        return None
    return 1e3 * span["seconds"] / r["batches"]
