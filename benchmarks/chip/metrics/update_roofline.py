"""% of the HBM roofline the row update reached: per step, the bytes the
update must move on one device at peak HBM bandwidth, over the device
time of ``update_ms``'s ops. The bytes, with duplicate ids counted as
they occur: each slot's gradient row read once, and its rows of every
table the update writes read once and written once. FFM with AdaGrad
(``latent`` in the config): W and G rows of fields · latent floats, 5 row
moves a slot. FM with SGD: the w and v rows, 1 + E floats (on a mesh
whose ``model`` axis splits v: E / |model| of them a device), 3 row
moves a slot. Nothing when no such op ran."""

import importlib.util
import os


def _update_ms():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "update_ms.py")
    spec = importlib.util.spec_from_file_location("bench_metric_update_ms_ops", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def update_bytes(cfg, traffic):
    slots = int(traffic["batch"]) * int(cfg["fields"])
    if "latent" in cfg:
        return slots * int(cfg["fields"]) * int(cfg["latent"]) * 4 * 5
    e = int(cfg["embed_dim"])
    mesh = cfg.get("mesh") or {}
    if "model" in (cfg.get("param_specs", {}).get("v") or []):
        e //= int(mesh["shape"][mesh["axes"].index("model")])
    return slots * (1 + e) * 4 * 3


def read(r):
    p = r["trace"]["programs"].get(r["step_name"])
    if not p or p["count"] <= 0:
        return None
    seconds = _update_ms().update_seconds(r)
    if seconds <= 0:
        return None
    t_min = update_bytes(r["cfg"], r["traffic"]) / r["peaks"]["hbm_bytes_per_s"]
    return 100.0 * p["count"] * t_min / seconds
