"""A scoped block's share of its roofline in percent: the least time the
chip could take for the work the builder's ``kernels`` entry states for one
step (the larger of operations over peak FLOP/s and bytes over peak
bytes/s, ``kernel_roofline.floor_seconds``), over the device time of the
operations the program traced under the scope (``scope_ms_per_step``'s
selection).  ``params``: ``kernel`` names the builder's entry, ``scopes``
the ``hvd.*`` scopes whose operations did that work.  A cell with no such
entry, a run with no trace, a program that names no scopes and a scope no
operation carries all read nothing."""

from yardstick.readers import kernel_roofline, scope_ms_per_step


def read(ev, params):
    floor = kernel_roofline.floor_seconds(ev, params)
    took = scope_ms_per_step.read(ev, {"scopes": params["scopes"]})
    if floor is None or not took:
        return None
    return 100.0 * floor[0] / (took / 1e3)
