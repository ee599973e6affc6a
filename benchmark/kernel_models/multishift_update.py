"""Bytes and operations the live-shift update of a multi-shift CG
NEEDS, from shapes: ``x_i += alpha_i p_i``, ``p_i = zeta_i r + beta_i
p_i`` on ``n_rhs`` live shifts of one parity field (the kernel
``multishift_update_pallas`` of ``ops/blas_pallas.py``).

Per live shift and site of the parity field (half the lattice):
``x_i`` and ``p_i`` read once and written once, ``spins x colours``
complex numbers each.

    f32, a 4-spinor: 24 * (4 + 4) * 2 = 384 B a site and live shift
    (15.9 MB a vector at 24^4: 63.7 MB a live shift and iteration)

NOT needed, and not counted: ``r`` (one vector an iteration whatever
the number of live shifts, 1 / (4 n) of the traffic; where the field is
small the compiler may carry it on chip, so leaving it out keeps the
count a lower bound), the three (N,) coefficient rows, the rows of the
shifts that have converged (the kernel's index maps revisit the last
live block and move nothing).  ``link_bytes`` is the reader's name for
the last operand's width and is not used.  Three multiply-adds and an
add a real: 6 flop a real number of a live shift.
"""


def needed(lattice, link_bytes=4, in_bytes=4, out_bytes=4, n_rhs=1,
           spins=4, colours=3):
    """{"bytes", "flops", "sites"} of the update of ``n_rhs`` live
    shifts on ``lattice`` (the four extents; the field is one parity)."""
    sites = 1
    for d in lattice:
        sites *= int(d)
    sites //= 2
    reals = 2 * int(spins) * int(colours)
    per_site = 2 * reals * n_rhs * (in_bytes + out_bytes)
    return {"sites": sites, "bytes": sites * per_site,
            "bytes_per_site": per_site,
            "flops": sites * 6 * reals * n_rhs}
