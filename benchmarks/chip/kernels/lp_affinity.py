"""Bytes of one call of the ``lp_affinity`` Pallas kernel, from its shapes
as the trace gives them.

The call reads the neighbour labels (s32) and edge weights (f32) of an ELL
block, ``(..., rows, width)`` (a batched call carries a leading batch axis
on the labels; the weights are shared), and writes the ``(..., rows,
k_pad)`` f32 affinities.  Its arithmetic (a compare, a select and an add
per row, ELL column and block) runs on the vector unit in 32 bits, for
which TPU v5e has no published peak (its 197 TFLOP/s are bf16 matrix
operations), so the kernel's least time is its bytes over the HBM
bandwidth alone.
"""
from benchmarks.chip.devtrace import nbytes


def cost(results, operands) -> int:
    """(result shapes, operand shapes) → bytes read and written."""
    return sum(nbytes(s) for s in list(operands) + list(results))
