"""Share of set-up's compile requests that the persistent compile cache
answered, in percent: JAX's own monitoring events (``measure.
CompileCounter``), set-up only.  100 from a cell's second run in a
checkout on."""


def read(ev, params):
    cache = ev["cache"]
    if not cache["requests"]:
        return None
    return 100.0 * cache["hits"] / cache["requests"]
