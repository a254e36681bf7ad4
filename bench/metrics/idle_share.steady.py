"""Device: share of the traced slice with no operation on the device,
averaged over the cell's chips (%)."""


def read(run):
    w = run.traced_s()
    return 100.0 * (1.0 - run.device_busy_s() / w) if w > 0 else None
