"""Bus bandwidth of the gradient exchange in GB/s: NCCL's convention,
2(n-1)/n x the gradient bytes of a step (from the shapes), over the time a
step had a collective under way on the traced chip, hidden behind compute
or not (``collective_flight_s``).  Against the chip's 1,600 Gbit/s of
interconnect it says how far the exchange is from the links' limit."""

from yardstick import flops


def read(ev, params):
    traced = ev.get("traced") or {}
    if "reduction" not in traced or ev["chips"] < 2:
        return None
    secs = traced["reduction"]["collective_flight_s"] / traced["steps"]
    if not secs:
        return None
    return flops.allreduce_bus_bytes(ev["grad_bytes"], ev["chips"]) \
        / secs / 1e9
