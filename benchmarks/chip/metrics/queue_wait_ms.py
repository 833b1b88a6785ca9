"""ms per step the training loop waited on the pipeline's staged-batch
queue (the transfer thread not yet done with the next batch): the
``dmlc:xfer_queue_wait`` spans (``trace["spans"]``, clipped to the
window), over the steps dispatched in the window. Nothing when the span
never occurred."""


def read(r):
    span = r["trace"].get("spans", {}).get("dmlc:xfer_queue_wait")
    if not r["dispatched"] or span is None:
        return None
    return 1e3 * span["seconds"] / r["dispatched"]
