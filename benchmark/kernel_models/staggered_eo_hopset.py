"""Bytes and operations ONE pass of the two-pass improved staggered
hop NEEDS, from shapes: the scatter form ``dslash_staggered_eo_pallas_v3``
launches the fat (one-hop) and the Naik (three-hop) set as a kernel
each and XLA adds the two results.

Per output site of one pass: the 8 link matrices of its hop set
(forward of its own parity, backward of the other, four directions),
each 18 reals, read once; the 6-real colour vector read once; its
6-real partial result written once.

    f32: 8*18*4 + 6*4 + 6*4 = 576 + 24 + 24 = 624 B per output site
    bf16 in, f32 out (the sloppy operator's call): 288 + 12 + 24 = 324 B

Two passes need 1,248 B where the hop as a whole needs 1,200
(``staggered_eo_fat_naik``): the second read of the colour vector and
the second result are what splitting the launch costs, and the XLA sum
of the two results is not in any kernel's count.  Half the hop's flops
a pass.
"""

from . import staggered_eo_fat_naik as hop

LINK_REALS = hop.LINK_REALS // 2
FLOPS_PER_SITE = hop.FLOPS_PER_SITE // 2


def needed(lattice, link_bytes=4, in_bytes=4, out_bytes=4, n_rhs=1):
    """{"bytes", "flops", "sites"} of one pass on ``lattice``."""
    sites = hop.needed(lattice)["sites"]
    per_site = (LINK_REALS * link_bytes
                + hop.SPINOR_REALS * n_rhs * (in_bytes + out_bytes))
    return {"sites": sites, "bytes": sites * per_site,
            "bytes_per_site": per_site,
            "flops": sites * FLOPS_PER_SITE * n_rhs}
