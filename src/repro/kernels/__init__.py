"""Pallas-TPU kernels for the paper's compute hot spots.

The paper's xPU accelerates NTT (iterative radix-2 NTTUs) and BConv
(tree-based BConvUs); its xMU fuses MemOps (IP + PMul).  Here those map to:

  ntt/        radix-2 negacyclic NTT, stages unrolled at trace time,
              one limb's polynomial resident in VMEM per grid step.
  bconv/      scale pass + tree-reduce pass over source limbs.
  fused_ip/   keyswitch inner product with optional fused PMul
              (the xMU "MemOp fusion" of Fig. 10(d)).
  modup/      one fused INTT -> BConv reduce -> NTT call per digit.

All kernels use uint32 Montgomery arithmetic built from 16-bit limb
partial products (``modops``) — TPU has no 64-bit integer multiply and no
mulhi, but 16x16->32 partials + carries are VPU-native.  Every limb is a
lane-dense ``(N/128, 128)`` block and the per-limb moduli and BConv
constants sit in SMEM, so Mosaic compiles each kernel for the TPU
(``tests/test_tpu_compile.py`` compiles them for a described v5e at
logN=16).  On the CPU they run with interpret=True and are validated
against pure-jnp oracles (ref.py) and the exact uint64 core
(repro.core.poly); on the chip, ``chip_smoke.py`` holds programs run on
the compiled kernels bit-identical to the jnp backend on the host CPU.
Large tables (twiddles, twists) are kernel operands, never captured
constants: jitted callers pass them in (``PolyContext.tables``).
"""
