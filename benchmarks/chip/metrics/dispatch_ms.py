"""ms per staged batch of dispatch and host-to-HBM hand-off:
``dispatch_pack + dispatch_put + dispatch_slot_wait`` of the window's
pipelines, over the batches staged in the window."""


def read(r):
    if not r["batches"]:
        return None
    s = r["stage_s"]
    return 1e3 * (s["dispatch_pack"] + s["dispatch_put"]
                  + s["dispatch_slot_wait"]) / r["batches"]
