"""ms per staged batch the pipeline's transfer thread waited for the
producer (fetch + parse + pack): ``stage_seconds["host_pull"]`` summed
over the window's pipelines, over the batches staged in the window."""


def read(r):
    if not r["batches"]:
        return None
    return 1e3 * r["stage_s"]["host_pull"] / r["batches"]
