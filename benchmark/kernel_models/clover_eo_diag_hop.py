"""Bytes and operations the fused clover kernel
``dslash_eo_pallas_diag_hop`` NEEDS, from shapes: A x + c * D t in one
pass, the second half of the even-odd clover operator.

Per output site: what ``clover_eo_post`` counts (links, the 144-real
blocks of this parity's clover term, the hop's spinor in, one spinor
out) plus the 24-real centre spinor x the blocks multiply, read once
at the width of the hop's input (both are the solver's iterates).

    f32: 1,344 + 24*4 = 1,440 B per output site;  bf16: 720 B

The sloppy operator calls this kernel with bf16 operands and an f32
result (the caller rounds after the combine): the count follows the
element types each traced call really had, 768 B there.  Same flops
as ``clover_eo_post`` plus the combine: 1320 + 504 + 48.
"""

from . import clover_eo_post as post

FLOPS_PER_SITE = post.FLOPS_PER_SITE + 48


def needed(lattice, link_bytes=4, in_bytes=4, out_bytes=4, n_rhs=1):
    """{"bytes", "flops", "sites"} of one call on ``lattice``."""
    base = post.needed(lattice, link_bytes, in_bytes, out_bytes, n_rhs)
    sites = base["sites"]
    per_site = (base["bytes_per_site"]
                + post.wilson.SPINOR_REALS * n_rhs * in_bytes)
    return {"sites": sites, "bytes": sites * per_site,
            "bytes_per_site": per_site,
            "flops": sites * FLOPS_PER_SITE * n_rhs}
