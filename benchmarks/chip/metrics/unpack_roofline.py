"""% of the HBM roofline the device unpack reached: per execution, the
bytes one device's unpack must read and write (``work.unpack_bytes``)
at peak HBM bandwidth, over the unpack programs' device time (XLA
modules whose name holds ``unpack``). Nothing when no unpack ran."""


def read(r):
    progs = [p for name, p in r["trace"]["programs"].items() if "unpack" in name]
    secs = sum(p["seconds"] for p in progs)
    runs = sum(p["count"] for p in progs)
    if secs <= 0 or runs <= 0:
        return None
    t_min = r["work"]["unpack_bytes"] / r["peaks"]["hbm_bytes_per_s"]
    return 100.0 * runs * t_min / secs
