"""% of the traced window in which no XLA op ran on the device (1 minus
the union of op intervals over the window), averaged over the devices."""


def read(r):
    t = r["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
