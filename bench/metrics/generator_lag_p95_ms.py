"""generator_lag_p95_ms: 95th percentile of how late the open-loop
generator submitted a request after its due time (harness clock)."""


def read(run):
    if run.loop != "open":
        return None
    lags = [(t.req.t_arrive - t.due) * 1e3 for t in run.window.requests
            if t.req is not None]
    return run.pctl(lags, 95)
