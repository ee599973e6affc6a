"""BLAS-shaped pallas kernels on the pair representation, and the
row-block arithmetic they share with other kernels of this layout.

``multishift_update_pallas`` is the live-prefix update of the
multi-shift CG (solvers/multishift.py): each grid step streams one
row-block of one shift through VMEM, in place.  ``_vmem_budget``,
``_tile_bytes`` and ``_pick_rows`` are the budget and the legal
row-blocks of an (rows, lanes) view (lanes = the trailing axis; block
second-to-minor extent divisible by 8 or equal to the array extent —
interpret mode does not enforce it, hardware does); ops/dwf_pallas.py
picks its blocks with them too.

The CG tail (x += a p; r -= a Ap; |r|^2) has no kernel here: XLA fuses
it under jit, in fewer passes on the chip than a kernel of its own
made (ROADMAP C5).

Layout: any REAL array (the pair-form representation every TPU solve
uses; complex solves keep the jnp path in ops/blas.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _vmem_budget() -> int:
    """Bytes one set of a kernel's blocks may hold in VMEM, the pipeline
    doubling it (QUDA_TPU_PALLAS_VMEM_MB, shared with the dslash
    kernels' _pick_bz)."""
    from ..utils import config as qconf
    return int(float(qconf.get("QUDA_TPU_PALLAS_VMEM_MB",
                               fresh=True)) * 2 ** 20)


def _tile_bytes(rows: int, cols: int, itemsize: int = 4,
                sublanes: int = 8) -> int:
    """Bytes of a (rows, cols) block as VMEM holds it: rows padded to
    the tile's sublanes, cols to 128 lanes."""
    return (-(-rows // sublanes) * sublanes * (-(-cols // 128) * 128)
            * itemsize)


def _pick_rows(R: int, C: int, nbufs: int, itemsize: int = 4) -> int:
    """Largest hardware-legal row-block of an (R, C) view whose ``nbufs``
    VMEM-resident buffers fit the scoped budget (``_vmem_budget``).
    Legality: block rows divisible by 8 or equal to R (round-5 Mosaic
    rule)."""
    budget = _vmem_budget()
    fitting = [br for br in range(1, R + 1)
               if R % br == 0 and (br % 8 == 0 or br == R)
               and nbufs * _tile_bytes(br, C, itemsize) <= budget]
    if not fitting:
        raise ValueError(
            f"no row-block of R={R} fits the VMEM budget at C={C} "
            f"(x{nbufs} buffers); use the jnp path (ops/blas.py)")
    return max(fitting)


def _as2d(x):
    return x.reshape(-1, x.shape[-1])


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def multishift_update_pallas(n_active, alpha_s, zeta, beta_s, x, p, r,
                             interpret: bool = False,
                             block_rows: int | None = None):
    """The shifted update of a multi-shift CG over its first
    ``n_active`` shifts, in place on the stacked iterates:
    ``x[i] += alpha_s[i] p[i]``, ``p[i] = zeta[i] r + beta_s[i] p[i]``
    for i < n_active (QUDA's multi_blas update with ``num_offset_now``);
    rows from ``n_active`` on are neither read nor written.  ``x``,
    ``p``: (N,) + r.shape, real; the three coefficients (N,);
    ``n_active`` a traced int32 in [1, N].

    Grid (row-block, shift), the shift innermost so ``r``'s block is
    fetched once a row-block; ``n_active`` and the coefficients are
    scalar-prefetched and the index maps of ``x`` and ``p`` clamp the
    shift to the last live one, so a skipped step revisits the block
    it holds and moves nothing; the results alias ``x`` and ``p``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ns, C = x.shape[0], x.shape[-1]
    R = r.size // C
    br = block_rows if block_rows is not None else _pick_rows(R, C, 5)
    if R % br != 0:
        raise ValueError(f"block_rows={br} does not divide rows={R}")
    # five blocks (x, p, r in; x, p out), double-buffered, as tiled
    need = 10 * _tile_bytes(br, C, x.dtype.itemsize)
    na = jnp.reshape(n_active, (1,)).astype(jnp.int32)
    coef = jnp.stack([alpha_s, zeta, beta_s]).astype(F32)

    def kernel(na_ref, coef_ref, x_ref, p_ref, r_ref, xo_ref, po_ref):
        s = pl.program_id(1)

        @pl.when(s < na_ref[0])
        def _():
            pv = p_ref[0].astype(F32)
            xo_ref[0] = (x_ref[0].astype(F32)
                         + coef_ref[0, s] * pv).astype(xo_ref.dtype)
            po_ref[0] = (coef_ref[1, s] * r_ref[...].astype(F32)
                         + coef_ref[2, s] * pv).astype(po_ref.dtype)

    row = pl.BlockSpec((1, br, C), lambda i, s, na, coef:
                       (jnp.minimum(s, na[0] - 1), i, 0))
    xo, po = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(R // br, ns),
            in_specs=[row, row,
                      pl.BlockSpec((br, C), lambda i, s, na, coef: (i, 0))],
            out_specs=[row, row]),
        out_shape=[jax.ShapeDtypeStruct((ns, R, C), x.dtype),
                   jax.ShapeDtypeStruct((ns, R, C), p.dtype)],
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 * 2 ** 20, need + 4 * 2 ** 20)),
        interpret=interpret,
    )(na, coef, x.reshape(ns, R, C), p.reshape(ns, R, C), _as2d(r))
    return xo.reshape(x.shape), po.reshape(p.shape)
