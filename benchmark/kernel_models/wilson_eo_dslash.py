"""Bytes and operations one even-odd Wilson dslash NEEDS, from shapes.

Per output site (half the lattice): the 8 links it multiplies by, each
18 reals, read once; one 24-real spinor read for every output site (each
input site is some output's neighbour eight times over, but the
algorithm needs it from memory once); one 24-real spinor written.

    f32: 8*18*4 + 24*4 + 24*4 = 576 + 96 + 96 = 768 B per output site

``link_bytes`` / ``in_bytes`` / ``out_bytes`` are the widths of what is
stored (4 for f32, 2 for bf16: the sloppy kernels read bf16 links and
spinors and write bf16 or f32); ``n_rhs`` sources stream through one
read of the links (the MRHS kernel).
Re-reads of neighbour tiles, halo padding and anything else the
implementation does on top are NOT needed bytes: they are what the
roofline share is there to show.  (``obs/roofline.KERNEL_MODELS`` charges
each psi tile five times, 1152 B; not used.)  1320 flop per output site
(QUDA's Dslash::flops).
"""

LINK_REALS = 8 * 18
SPINOR_REALS = 24
FLOPS_PER_SITE = 1320


def needed(lattice, link_bytes=4, in_bytes=4, out_bytes=4, n_rhs=1):
    """{"bytes", "flops", "sites"} of one dslash call on ``lattice``
    (the four extents; the output is one parity)."""
    sites = 1
    for d in lattice:
        sites *= int(d)
    sites //= 2
    per_site = (LINK_REALS * link_bytes
                + SPINOR_REALS * n_rhs * (in_bytes + out_bytes))
    return {"sites": sites, "bytes": sites * per_site,
            "bytes_per_site": per_site,
            "flops": sites * FLOPS_PER_SITE * n_rhs}
