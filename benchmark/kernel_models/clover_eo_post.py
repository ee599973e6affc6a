"""Bytes and operations the fused clover kernel ``dslash_eo_pallas_post``
NEEDS, from shapes: one even-odd Wilson dslash with the chiral blocks
of the other parity's inverse clover term applied to its result,
E(D psi), in one pass.

Per output site: what ``wilson_eo_dslash`` counts (8 links x 18 reals
once, one 24-real spinor in, one out) plus the site's two chiral 6x6
complex blocks read once: 2*6*6 = 72 complex numbers, 144 reals, as
the program keeps them (QUDA's packed order keeps the Hermitian half,
72 reals; the kernel as written multiplies by all of them).

    f32: 768 + 144*4 = 1,344 B per output site;  bf16: 672 B

Links, spinor and blocks share one storage type in this kernel, so the
element types the trace reduction prints (result, first operand =
spinor, last operand = blocks) carry all three widths: ``link_bytes``
is the width of links AND blocks.  1320 + 504 flop per output site
(the dslash and two 6x6 complex matrix-vector products).
"""

from . import wilson_eo_dslash as wilson

BLOCK_REALS = 2 * 6 * 6 * 2
FLOPS_PER_SITE = wilson.FLOPS_PER_SITE + 504


def needed(lattice, link_bytes=4, in_bytes=4, out_bytes=4, n_rhs=1):
    """{"bytes", "flops", "sites"} of one call on ``lattice`` (the four
    extents; the output is one parity)."""
    base = wilson.needed(lattice, link_bytes, in_bytes, out_bytes, n_rhs)
    sites = base["sites"]
    per_site = base["bytes_per_site"] + BLOCK_REALS * link_bytes
    return {"sites": sites, "bytes": sites * per_site,
            "bytes_per_site": per_site,
            "flops": sites * FLOPS_PER_SITE * n_rhs}
