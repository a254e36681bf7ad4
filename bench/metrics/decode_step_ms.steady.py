"""Model step: host seconds of the decode blocks over the decode steps
they ran, in the window (ms per step; every drive together)."""


def read(run):
    steps = run.delta("decode_steps")
    return 1e3 * run.delta("decode_s") / steps if steps > 0 else None
