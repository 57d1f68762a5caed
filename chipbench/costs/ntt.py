"""Forward or inverse NTT kernel (``kernels/ntt``).

A call's operands are two per-limb vectors, the data (``R`` rows of
``N/128 x 128`` uint32 words: ``R/a`` polynomials of ``a`` limbs) and
two twiddle tables of the ``a`` limbs; the result has the data's shape.
"""


def is_call(result, operands) -> bool:
    ranks = [len(d) for _, d in operands]
    return ranks == [1, 1, 3, 3, 3] and len(result) == 1


def min_bytes(result, operands) -> int:
    """The data read once and the result written once.  The twiddle
    tables are not counted: a kernel may make them on the fly."""
    from chipbench.kernels import nbytes

    return nbytes(operands[2]) + nbytes(result[0])
