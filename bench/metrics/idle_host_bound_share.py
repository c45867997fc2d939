"""idle_host_bound_share: share of the traced window in which no device
op runs while the program does host work on the path (a ``repro.``
``schedule``, ``assemble``, ``dispatch``, ``copy`` or ``extract``
annotation is open): the idle the host causes, not idle with no work in
hand."""


def read(run):
    prog = getattr(run, "program", None)
    tr = run.trace
    if (not prog or prog["host_bound_idle_s"] is None or not tr
            or not tr["window_s"]):
        return None
    return 100.0 * prog["host_bound_idle_s"] / tr["window_s"]
