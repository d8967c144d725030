"""Host clock around the first step: compilation or the cache read, the
first execution, and blocking on its loss."""


def read(ev, params):
    return ev["first_step_s"]
