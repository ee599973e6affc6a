"""Bytes and operations the even-odd Wilson hop WITH THE COMBINE EPILOGUE
needs, from shapes: ``[g5] (xc + coeff * hop)`` and its per-source sums
of squares in one kernel (``dslash_eo_pallas_packed_mrhs_combine``, the
second hop of each ``M`` of the batched operator).

Per output site: what the bare hop needs (``wilson_eo_dslash``: the 8
links once, one 24-real spinor in and one out per source) and the
24-real ``xc`` tile per source the epilogue adds to it, read once.

    f32, eight sources: 576 + 8 * (96 + 96 + 96) = 2,880 B per output site
    (the bare hop: 2,112)

The element types the trace reduction prints are the result's, the first
operand's (psi) and the last's (the links); ``xc`` is in neither place.
It is the operator's own input ``x`` of ``x - kappa^2 D D x``, stored as
the hopped spinor is, so it is charged ``in_bytes``.  NOT needed, and
not counted: the epilogue's partial sums (one (8, 288) f32 block a grid
step, 0.88 MB a call at 24^4: the algorithm needs N floats), re-reads of
neighbour tiles, padding lanes.  1320 flop per output site for the hop,
and per spinor real one multiply-add for the combine and one for the
sum of squares: 96 more.
"""

from . import wilson_eo_dslash as wilson

FLOPS_PER_SITE = wilson.FLOPS_PER_SITE + 4 * wilson.SPINOR_REALS


def needed(lattice, link_bytes=4, in_bytes=4, out_bytes=4, n_rhs=1):
    """{"bytes", "flops", "sites"} of one call on ``lattice`` (the four
    extents; the output is one parity)."""
    base = wilson.needed(lattice, link_bytes, in_bytes, out_bytes, n_rhs)
    sites = base["sites"]
    per_site = base["bytes_per_site"] + wilson.SPINOR_REALS * n_rhs * in_bytes
    return {"sites": sites, "bytes": sites * per_site,
            "bytes_per_site": per_site,
            "flops": sites * FLOPS_PER_SITE * n_rhs}
