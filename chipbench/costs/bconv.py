"""Basis conversion kernel (``kernels/bconv``), two calls per
conversion: a scaling pass (three per-limb vectors and the ``src``
limbs, result ``src`` limbs) and a reduction pass (the ``src x dst``
constants, two per-limb vectors of the target and the scaled limbs,
result ``dst`` limbs)."""


def is_call(result, operands) -> bool:
    ranks = [len(d) for _, d in operands]
    return ranks in ([1, 1, 1, 3], [2, 1, 1, 3]) and len(result) == 1


def min_bytes(result, operands) -> int:
    """The input limbs read once and the output limbs written once; the
    constants are not counted.  Fusing the two passes into one kernel
    makes this count stale: the scaled limbs then never reach HBM."""
    from chipbench.kernels import nbytes

    return nbytes(operands[-1]) + nbytes(result[0])
