"""Share of the traced window in which no operation ran on the traced
chip, in percent: 1 - (union of the operations' intervals / window)."""


def read(ev, params):
    traced = ev.get("traced") or {}
    if "reduction" not in traced:
        return None
    red = traced["reduction"]
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
