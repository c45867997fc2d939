"""schedule_ms_per_query: host time inside the server's ``_schedule``
(term resolution, block selection, fusion) per answered request, from the
harness's spans around it."""


def read(run):
    if not run.spans or not run.spans["schedule"] or not run.served():
        return None
    busy = sum(b - a for a, b in run.spans["schedule"])
    return busy * 1e3 / run.served()
