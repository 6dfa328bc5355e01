"""Bytes of one call of the ``pin_affinity`` Pallas kernel (which
``ops.pin_count`` calls), from its shapes as the trace gives them.

The call reads the pin labels (s32) and pin mask (f32) of a net→pin ELL
block, ``(..., nets, width)`` (a batched call carries a leading batch axis
on the labels), and the ``(nets, 1)`` f32 net weights, and writes two
``(..., nets, k_pad)`` f32 results: pin counts and weighted scores.  Its
arithmetic runs on the vector unit in 32 bits, for which TPU v5e has no
published peak, so the kernel's least time is its bytes over the HBM
bandwidth alone.
"""
from benchmarks.chip.devtrace import nbytes


def cost(results, operands) -> int:
    """(result shapes, operand shapes) → bytes read and written."""
    return sum(nbytes(s) for s in list(operands) + list(results))
