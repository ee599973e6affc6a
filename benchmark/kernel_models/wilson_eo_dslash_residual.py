"""Bytes and operations the even-odd Wilson hop WITH THE RESIDUAL FORM
of the combine epilogue needs, from shapes: ``rc - alpha * [g5] (xc +
coeff * hop)`` and its per-source sums of squares in one kernel
(``dslash_eo_pallas_packed_mrhs_residual``, the last hop of a batched
CG iteration: ``xc = g5 M p``, ``rc = r``, the result the new ``r``).

Per output site: what the hop with the combine epilogue needs
(``wilson_eo_dslash_combine``: the 8 links once, one 24-real spinor in,
one ``xc`` tile in and one result out per source) and the 24-real ``rc``
tile per source the residual form reads besides, once.

    f32, eight sources: 576 + 8 * (96 + 96 + 96 + 96) = 3,648 B per
    output site (the combine hop: 2,880; the bare hop: 2,112)

``rc`` is the solver's residual, stored as the hopped spinor is, so it
is charged ``in_bytes``, as ``xc`` is.  NOT needed, and not counted: the
epilogue's partial sums (0.88 MB a call at 24^4: the algorithm needs N
floats), the N floats of ``alpha``, re-reads of neighbour tiles, padding
lanes.  Per spinor real one multiply-add more than the combine form.
"""

from . import wilson_eo_dslash as wilson
from . import wilson_eo_dslash_combine as combine

FLOPS_PER_SITE = combine.FLOPS_PER_SITE + 2 * wilson.SPINOR_REALS


def needed(lattice, link_bytes=4, in_bytes=4, out_bytes=4, n_rhs=1):
    """{"bytes", "flops", "sites"} of one call on ``lattice`` (the four
    extents; the output is one parity)."""
    base = combine.needed(lattice, link_bytes, in_bytes, out_bytes, n_rhs)
    sites = base["sites"]
    per_site = base["bytes_per_site"] + wilson.SPINOR_REALS * n_rhs * in_bytes
    return {"sites": sites, "bytes": sites * per_site,
            "bytes_per_site": per_site,
            "flops": sites * FLOPS_PER_SITE * n_rhs}
