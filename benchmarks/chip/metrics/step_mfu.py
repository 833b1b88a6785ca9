"""% of the chips' peak the whole step reached: steps run in the traced
window times the least time one step's required work takes on one chip
(the larger of FLOPs at peak FLOP/s and bytes at peak HBM bandwidth,
``work.step_work``), over the traced window times the chips."""


def read(r):
    p = r["trace"]["programs"].get(r["step_name"])
    if not p or p["count"] <= 0:
        return None
    w, pk = r["work"]["step"], r["peaks"]
    t_min = max(w["flops"] / pk["flops_per_s"], w["bytes"] / pk["hbm_bytes_per_s"])
    return 100.0 * p["count"] * t_min / (r["trace"]["window_s"] * r["chips"])
