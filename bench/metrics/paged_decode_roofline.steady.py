"""Kernels: the paged decode kernel's share of its roofline in the traced
slice (%): the least time its calls could take, from the live context of
each decoding slot, over the device time of its events."""


def read(run):
    t = run.kernel_s("paged_decode")
    if t <= 0 or run.traced_kernel_calls.get("paged_decode", 0) == 0:
        return None
    return 100.0 * run.traced_kernel_ideal_s["paged_decode"] / t
