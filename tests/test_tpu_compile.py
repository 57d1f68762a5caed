"""Compile every Pallas kernel of the keyswitch path for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU
lowering refuses: blocks that are not (8, 128)-tileable, shape casts
Mosaic cannot lay out, i64 indices, scoped-VMEM overruns.  These tests
compile each kernel with ``interpret=False`` for one chip of a
*described* ``v5e:2x2`` topology — the TPU compiler runs here without a
chip — at logN=16 with the ``PAPER_PARAMS`` limb counts (L=35, k=12,
alpha=12, dnum=3): 48 extended-basis limbs, full 12-limb digits, the
short 11-limb last digit of level 34, and ModDown's 12 -> 36 BConv.

Only shapes are needed, so no tables are built.  The topology is
described inside a module fixture (never at import), and the persistent
compilation cache is off around the compiles: an entry written for a
described chip cannot be read back without one.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.params import PAPER_PARAMS
from repro.kernels.bconv.bconv import bconv_pallas
from repro.kernels.fused_ip.fused_ip import fused_ip_pallas
from repro.kernels.modops import lane_layout
from repro.kernels.modup.modup import modup_pallas
from repro.kernels.ntt.ntt import AUX_ROWS, ntt_pallas

P = PAPER_PARAMS
LOGN = P.logN
R, LANES = lane_layout(P.N)
L_EXT = P.L + 1 + P.k                     # 48 limbs of Q_L u P
DNUM = P.dnum


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _limbs(n):
    return (n, R, LANES)


def _tables(n):
    return (n, R + AUX_ROWS, LANES)


@pytest.mark.parametrize("inverse", [False, True], ids=["fwd", "inv"])
def test_ntt_compiles(one_chip, inverse):
    def fn(x, twist, tw, q, qinv):
        return ntt_pallas(x, twist, tw, q, qinv, logn=LOGN,
                          inverse=inverse, interpret=False)

    _compile(fn, one_chip, _limbs(2 * L_EXT), _limbs(L_EXT),
             _tables(L_EXT), (L_EXT,), (L_EXT,))


def test_bconv_compiles(one_chip):
    """ModDown's P -> Q conversion of both accumulators: 2 x 12 -> 36."""
    k, l = P.k, P.L + 1

    def fn(x, qi, sq, sqn, c, dq, dqn):
        return bconv_pallas(x, qi, sq, sqn, c, dq, dqn, interpret=False)

    _compile(fn, one_chip, _limbs(2 * k), (k,), (k,), (k,), (k, l), (l,),
             (l,))


@pytest.mark.parametrize("with_pt", [False, True], ids=["ip", "ip_pmul"])
def test_fused_ip_compiles(one_chip, with_pt):
    def fn(d, evk, q, qn, *pt):
        return fused_ip_pallas(d, evk, pt[0] if pt else None, q, qn,
                               interpret=False)

    shapes = [(DNUM,) + _limbs(L_EXT), (DNUM, 2) + _limbs(L_EXT),
              (L_EXT,), (L_EXT,)]
    if with_pt:
        shapes.append(_limbs(L_EXT))
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("ls,ld", [(P.alpha, L_EXT), (P.alpha - 1, L_EXT - 1)],
                         ids=["full_digit", "short_last_digit"])
def test_fused_modup_compiles(one_chip, ls, ld):
    """One digit at level 35 (12 -> 48 limbs) and the 11-limb last digit
    of level 34 (11 -> 47), batch 4 in the grid."""
    def fn(x, tsc, twi, sq, sqn, c, twf, twtf, dq, dqn):
        return modup_pallas(x, tsc, twi, sq, sqn, c, twf, twtf, dq, dqn,
                            logn=LOGN, interpret=False)

    compiled = _compile(fn, one_chip, _limbs(4 * ls), _limbs(ls),
                        _tables(ls), (ls,), (ls,), (ls, ld), _limbs(ld),
                        _tables(ld), (ld,), (ld,))
    assert compiled.memory_analysis() is not None
    assert np.prod(_limbs(4 * ld)) * 4 == compiled.memory_analysis(
    ).output_size_in_bytes
