"""Fused ModUp kernel (``kernels/modup``): one digit of ``s`` limbs
through INTT, basis conversion and NTT to ``d`` limbs, in one call.

Operands: per-limb vectors of the digit, the ``s x d`` conversion
matrix, per-limb vectors of the target, the digit (``s`` limbs of
``N/128 x 128`` words) with its two twiddle tables, and the target's
two twiddle tables; the result has ``d`` limbs.
"""


def is_call(result, operands) -> bool:
    ranks = [len(d) for _, d in operands]
    return ranks == [1, 1, 2, 1, 1, 3, 3, 3, 3, 3] and len(result) == 1


def min_bytes(result, operands) -> int:
    """The digit read once and the result written once; tables and
    constants are not counted."""
    from chipbench.kernels import nbytes

    digit_shaped = operands[5]   # (s, N/128, 128): as large as the digit
    return nbytes(digit_shaped) + nbytes(result[0])
