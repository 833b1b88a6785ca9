"""ms per step the training loop waited for its next staged batch: the
harness's own ``bench:input_wait`` span around the feed's ``next``
(epoch boundaries included), over the steps dispatched in the window."""


def read(r):
    if not r["dispatched"]:
        return None
    return 1e3 * r["input_wait_s"] / r["dispatched"]
