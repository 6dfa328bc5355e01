"""Roofline share of the ``pin_affinity`` Pallas kernel over the traced
window, in %: least time over summed device time of its calls.  The least
time is the bytes term alone (no published peak fits its 32-bit vector
arithmetic); see ``kernels/pin_affinity.py``."""
from benchmarks.chip.devtrace import roofline_share


def read(ctx):
    return roofline_share(ctx.trace, "pin_affinity",
                          ctx.kernel_cost("pin_affinity"), ctx.peaks)
