"""% of the traced window in which the device was idle while a ring
worker was inside ``dmlc:ring_dispatch`` (DMA and unpack dispatch):
``trace["idle_overlap"]``, the union of the span over all threads
intersected with each device's idle, averaged over the devices, over
the window. Nothing when the span never occurred."""


def read(r):
    t = r["trace"]
    held = t.get("idle_overlap", {}).get("dmlc:ring_dispatch")
    if t["window_s"] <= 0 or held is None:
        return None
    return 100.0 * held / t["window_s"]
