"""Bytes and operations one application of a Möbius s-operator NEEDS,
from shapes: the real (Ls, Ls) chirality blocks on every site of a 5-d
parity vector, ``out[s] = sum_t B_c[s, t] x[t]`` (the kernel
``mobius_sblock_pallas`` of ``ops/dwf_pallas.py``; ``B_c`` the ``+``
block on spin rows 0, 1 and the ``-`` block on rows 2, 3).

Per 4-d output site (half the lattice): Ls planes of one 24-real spinor
read once, Ls planes of one 24-real spinor written.

    bf16 -> bf16, Ls 12: 12 * 24 * (2 + 2) = 1,152 B per 4-d site
    f32 -> f32,   Ls 12: 12 * 24 * (4 + 4) = 2,304 B per 4-d site

``in_bytes`` / ``out_bytes`` are the widths of what is stored (the
trace's first operand and result); ``n_rhs`` is Ls, the planes that
stream through one call.  NOT needed, and not counted: the two (Ls, Ls)
blocks (2 Ls^2 floats a call, in SMEM; ``link_bytes`` is the reader's
name for the last operand's width and is not used), padding lanes and
sublanes of the (Z, YXh) tiles (a 24 x 288 bf16 plane is stored 32 x
384).  The accumulate form ``y + a B x`` reads one vector more and is
another kernel with another name; this model is not its.  Per 4-d site
and plane 24 x Ls multiply-adds: the blocks are dense (EOFA's are).
"""

SPINOR_REALS = 24


def needed(lattice, link_bytes=4, in_bytes=4, out_bytes=4, n_rhs=1):
    """{"bytes", "flops", "sites"} of one call on ``lattice`` (the four
    extents; the vector is one 4-d parity, ``n_rhs`` = Ls planes)."""
    sites = 1
    for d in lattice:
        sites *= int(d)
    sites //= 2
    per_site = SPINOR_REALS * n_rhs * (in_bytes + out_bytes)
    return {"sites": sites, "bytes": sites * per_site,
            "bytes_per_site": per_site,
            "flops": sites * 2 * SPINOR_REALS * n_rhs * n_rhs}
