"""Growth of one of the program's counters (``hvd.metrics_snapshot()``,
summed over its series) over the window, a step, on rank 0.  A count."""


def read(ev, params):
    if params["counter"] not in ev["counters"] or not ev["steps"]:
        return None
    return ev["counters"][params["counter"]] / ev["steps"]
