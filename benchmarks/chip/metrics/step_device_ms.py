"""ms of device time per execution of the training step program
(``config.json``'s ``step_name``), averaged over the devices."""


def read(r):
    p = r["trace"]["programs"].get(r["step_name"])
    if not p or p["count"] <= 0:
        return None
    return 1e3 * p["seconds"] / p["count"]
