"""queue_wait_p95_ms: 95th percentile of the time from a request's due
time to its admission into a flush (the server's ``Request.t_admit``)."""


def read(run):
    waits = [(t.req.t_admit - t.due) * 1e3 for t in run.window.requests
             if t.ok]
    return run.pctl(waits, 95)
