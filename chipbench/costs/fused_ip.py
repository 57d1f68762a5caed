"""Fused inner-product kernel (``kernels/fused_ip``): ``dnum`` digits
of ``e`` limbs times the key's ``dnum x 2`` digit polynomials, optionally
times a plaintext, accumulated into two ``e``-limb results.

Operands: two per-limb vectors, the digits ``(dnum, e, N/128, 128)``,
the key ``(dnum, 2, e, N/128, 128)`` and, with a plaintext, its ``e``
limbs; the result is a pair of ``e``-limb arrays."""


def is_call(result, operands) -> bool:
    ranks = [len(d) for _, d in operands]
    return ranks[:4] == [1, 1, 4, 5] and len(result) == 2


def min_bytes(result, operands) -> int:
    """Digits, key and plaintext read once, both results written once.
    A kernel that also applies the rotations' automorphisms or
    accumulates several rotations (ROADMAP A5) makes this count stale."""
    from chipbench.kernels import nbytes

    return sum(nbytes(o) for o in operands[2:]) + sum(map(nbytes, result))
