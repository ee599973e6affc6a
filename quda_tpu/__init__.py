"""quda_tpu — a TPU-native lattice QCD framework.

A from-scratch JAX/XLA/Pallas re-design with the capabilities of QUDA
(https://github.com/lattice/quda): Dirac stencils, mixed-precision Krylov
solvers, adaptive multigrid, eigensolvers, and the HMC gauge sector —
built on sharded jax.Arrays over a 4-D device mesh with XLA collectives
for halo exchange.

Subpackages
-----------
fields    lattice geometry, ColorSpinorField / GaugeField / CloverField
ops       stencils, BLAS/reductions, SU(3) algebra, gamma algebra
models    Dirac operator classes (Wilson, clover, twisted, staggered, DWF...)
solvers   CG family, BiCGStab(L), GCR, CA solvers, multi-shift, mixed prec
mg        adaptive multigrid (transfer, coarse ops, V-cycle)
eig       TRLM / IRAM eigensolvers, Chebyshev acceleration, deflation
gauge     HMC forces, smearing, gauge fixing, observables, heatbath
parallel  device mesh, sharding layouts, halo exchange
utils     tuning cache, profiling, RNG, I/O, checkpointing
interfaces  C-ABI shim and MILC-style entry points
"""

__version__ = "0.1.0"

import os as _os

import jax as _jax

# f32 means f32.  On a TPU the DEFAULT matmul precision multiplies f32
# (and complex64) operands in ONE bf16 pass on the MXU: every einsum of
# the XLA-path operators — the complex reference DiracWilson.M that
# certifies each solve's true residual, the split-grid solve, SU(3)
# algebra — then carries ~2e-3 relative error.  Observed on a v5e (PR
# 22): a 24^4 solve converged to 1e-6 on the f32 pallas operator was
# reported at true_res 2.5e-3 by the complex check.  An explicit
# JAX_DEFAULT_MATMUL_PRECISION from outside is left alone.  The pallas
# stencil kernels multiply on the VPU and never depended on this.
if "JAX_DEFAULT_MATMUL_PRECISION" not in _os.environ:
    _jax.config.update("jax_default_matmul_precision", "highest")

from .fields.geometry import EVEN, FULL, ODD, LatticeGeometry  # noqa: F401
