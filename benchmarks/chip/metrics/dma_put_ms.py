"""ms per staged batch the ring workers spent in the host-to-HBM
``jax.device_put`` call(s): the ``dmlc:dma_put`` spans of every thread
(``trace["spans"]``, clipped to the window), over the batches staged in
the window. Nothing when the span never occurred."""


def read(r):
    span = r["trace"].get("spans", {}).get("dmlc:dma_put")
    if not r["batches"] or span is None:
        return None
    return 1e3 * span["seconds"] / r["batches"]
