"""qps: answers per second, counted over the window: every request answered
by the window's close, over the time from its open to the last of those
answers (answers come in flush-sized bursts, so the window's own close
would cut one in part)."""


def read(run):
    w = run.window
    done = [t.req.t_done for t in w.requests if t.ok and t.req.t_done <= w.t_end]
    if not done:
        return None
    return len(done) / (max(done) - w.t0)
