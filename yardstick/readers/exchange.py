"""Host clock in the benchmark's worker, traced tail only: from the local
gradients being ready (``block_until_ready``) to every reduced gradient
being ready, median over the traced steps, in ms.  Only the eager job
stamps it."""

import statistics


def read(ev, params):
    stamps = (ev.get("traced") or {}).get("exchange_s")
    if not stamps:
        return None
    return 1e3 * statistics.median(stamps)
