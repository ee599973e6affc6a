"""Bytes and operations one even-odd improved staggered (fat + Naik)
hop NEEDS, from shapes.

Per output site (half the lattice): the 16 link matrices it multiplies
by (fat and long, forward of its own parity and backward of the other,
four directions each), each 18 reals, read once; one 6-real colour
vector read for every output site (each input site is some output's
neighbour sixteen times over, but the algorithm needs it from memory
once); one 6-real colour vector written.

    f32: 16*18*4 + 6*4 + 6*4 = 1,152 + 24 + 24 = 1,200 B per output site
    bf16 in, f32 out (the sloppy operator's call): 576 + 12 + 24 = 612 B

96 % of it links (Wilson: 768 B, 75 % links).  ``link_bytes`` /
``in_bytes`` / ``out_bytes`` are the widths of what is stored; ``n_rhs``
sources stream through one read of the links.  The program's own
comment counts "~864 B" for its fused kernel: that is the FULL-lattice
kernel, whose backward x/y/z hops re-use the forward link tiles it has
in VMEM, so it leaves out the other parity's six backward x/y/z
matrices (432 B) that the even-odd kernel fetches as operands of their
own; what the even-odd kernel moves is ~1,300 B (five psi tiles).
Re-reads of neighbour tiles and halo rows are NOT needed bytes.  1,146
flop per output site (QUDA's improved staggered Dslash::flops).
"""

LINK_REALS = 16 * 18
SPINOR_REALS = 6
FLOPS_PER_SITE = 1146


def needed(lattice, link_bytes=4, in_bytes=4, out_bytes=4, n_rhs=1):
    """{"bytes", "flops", "sites"} of one hop call on ``lattice`` (the
    four extents; the output is one parity)."""
    sites = 1
    for d in lattice:
        sites *= int(d)
    sites //= 2
    per_site = (LINK_REALS * link_bytes
                + SPINOR_REALS * n_rhs * (in_bytes + out_bytes))
    return {"sites": sites, "bytes": sites * per_site,
            "bytes_per_site": per_site,
            "flops": sites * FLOPS_PER_SITE * n_rhs}
