"""The MRHS pallas kernels in interpreter mode: the gauge-amortised
Wilson and staggered MRHS kernels (bit-match against the vmapped
single-RHS kernels), the Wilson kernel's two routes (full-Z tiles and
z-blocks) and its combine and residual epilogues.  Out of
tests/test_multirhs.py (the solvers and the API on them) so that each
half has a worker of its own: every distinct kernel shape costs a
20-25 s interpreter compile, and the unparametrised bit-match tests are
``slow`` for it."""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

KT, KZ, KY, KX = 4, 8, 4, 4          # kernel-test lattice extents


@pytest.mark.slow
@pytest.mark.parametrize("nrhs", [1, 3, 8])
def test_mrhs_kernel_bitmatches_vmapped_v2(nrhs):
    """dslash_pallas_packed_mrhs bit-matches jax.vmap of the single-RHS
    v2 kernel for N in {1, 3, 8} (N=1 is the degenerate case)."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    rng = np.random.default_rng(7)
    g = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * KX)), jnp.float32)
    psi_b = jnp.asarray(rng.standard_normal(
        (nrhs, 4, 3, 2, KT, KZ, KY * KX)), jnp.float32)
    gbw = wpp.backward_gauge(g, KX)
    want = jax.vmap(lambda p: wpp.dslash_pallas_packed(
        g, p, KX, interpret=True, gauge_bw=gbw))(psi_b)
    got = wpp.dslash_pallas_packed_mrhs(g, psi_b, KX, interpret=True,
                                        gauge_bw=gbw)
    assert bool(jnp.all(got == want))


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_mrhs_eo_kernel_bitmatches_all_parities(parity):
    """The eo MRHS kernel (the batched-solver hot path) bit-matches the
    single-RHS eo v2 kernel on both target parities, including N=1."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    dims = (KT, KZ, KY, KX)
    Xh = KX // 2
    rng = np.random.default_rng(8)
    u_here = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    u_there = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    u_bw = wpp.backward_gauge_eo(u_there, dims, parity)
    for nrhs in (1, 3):
        psi_b = jnp.asarray(rng.standard_normal(
            (nrhs, 4, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
        want = jnp.stack([wpp.dslash_eo_pallas_packed(
            u_here, u_bw, psi_b[i], dims, parity, interpret=True)
            for i in range(nrhs)])
        got = wpp.dslash_eo_pallas_packed_mrhs(
            u_here, u_bw, psi_b, dims, parity, interpret=True)
        assert bool(jnp.all(got == want)), (parity, nrhs)


# -- the MRHS kernel's two routes (ops/wilson_pallas_packed._mrhs_route) ---
#
# Full-Z tiles (three psi operands, the z shift wraps inside the tile,
# two time-slices a step) where their VMEM set fits, z-blocks (five)
# where it does not; both bitwise the single-source kernel per RHS.
# The single-source side is held to block_z = 8, so it really splices
# rows of its z-neighbour tiles; T = 4 so that a block's t neighbours
# are its own slice on one side and another block's on the other.

def _fz_dims(dtype, z_tiles=2):
    """(T, Z, Y, X) with Z = ``z_tiles`` sublane tiles of the storage
    dtype: two, and the full-Z body walks two chunks; one, and it works
    on the whole tile (as 24 rows of bf16 make it at 24^4)."""
    return (4, z_tiles * (8 if dtype == jnp.float32 else 16), 2, 4)


def _eo_mrhs_problem(dims, parity, nrhs, dtype, seed=8):
    from quda_tpu.ops import wilson_pallas_packed as wpp
    T, Z, Y, X = dims
    rng = np.random.default_rng(seed)

    def draw(shape):
        return jnp.asarray(rng.standard_normal(shape),
                           jnp.float32).astype(dtype)
    half = (T, Z, Y * X // 2)
    u_here = draw((4, 3, 3, 2) + half)
    u_bw = wpp.backward_gauge_eo(draw((4, 3, 3, 2) + half), dims, parity)
    return u_here, u_bw, draw((nrhs, 4, 3, 2) + half)


@pytest.fixture
def route_counts(tmp_path):
    """A metrics session of the test's own; calling the fixture reads
    ``wilson_mrhs_route_total`` as {route: count}, or by its other
    label, ``epilogue``."""
    from quda_tpu.obs import memory as omem
    from quda_tpu.obs import metrics as omet
    omet.stop(flush_files=False)
    omem.reset()
    omet.start(str(tmp_path))

    def read(label="route"):
        out = {}
        for (n, lab), v in omet.snapshot()["counters"].items():
            if n == "wilson_mrhs_route_total":
                out[dict(lab)[label]] = out.get(dict(lab)[label], 0.0) + v
        return out
    yield read
    omet.stop(flush_files=False)
    omem.reset()


@pytest.mark.parametrize("parity,nrhs,dtype,z_tiles", [
    (0, 2, jnp.float32, 2), (1, 8, jnp.float32, 2),
    (1, 2, jnp.bfloat16, 2),
    # 47 s alone; the bf16 two-tile kernel stays with the case above,
    # N = 8 with the f32 one
    pytest.param(0, 8, jnp.bfloat16, 2, marks=pytest.mark.slow),
    pytest.param(1, 2, jnp.float32, 2, marks=pytest.mark.slow),
    pytest.param(0, 8, jnp.float32, 1, marks=pytest.mark.slow),
    pytest.param(1, 8, jnp.bfloat16, 1, marks=pytest.mark.slow),
    pytest.param(0, 2, jnp.bfloat16, 2, marks=pytest.mark.slow)])
def test_mrhs_fullz_route_bitmatches_single_source(parity, nrhs, dtype,
                                                   z_tiles, route_counts):
    """The full-Z route is bitwise ``jax.vmap`` of the z-blocked
    single-source kernel, and ``wilson_mrhs_route_total`` counts it once
    per traced call (a second call of the same shapes traces nothing)."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    dims = _fz_dims(dtype, z_tiles)
    u_here, u_bw, psi_b = _eo_mrhs_problem(dims, parity, nrhs, dtype)
    want = jax.vmap(lambda p: wpp.dslash_eo_pallas_packed(
        u_here, u_bw, p, dims, parity, interpret=True,
        block_z=8))(psi_b)
    got = wpp.dslash_eo_pallas_packed_mrhs(
        u_here, u_bw, psi_b, dims, parity, interpret=True)
    again = wpp.dslash_eo_pallas_packed_mrhs(
        u_here, u_bw, psi_b, dims, parity, interpret=True)
    assert got.dtype == dtype
    assert bool(jnp.all(got == want)) and bool(jnp.all(again == want))
    assert route_counts() == {"fullz": 1.0}


def test_mrhs_zblock_route_runs_where_fullz_does_not_fit(route_counts):
    """A large tile (Z = 40, Y*Xh = 640: 60.9 MiB of full-Z blocks,
    one time-slice a step, against the 48 MiB the route may ask for)
    sends the call to the z-blocked five-operand route, from its shapes
    alone; it is still bitwise the single-source kernel and counted as
    ``zblock``."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    dims = (2, 40, 32, 40)
    assert wpp._mrhs_fullz_vmem(40, 640, jnp.float32, jnp.float32, 3)[1] \
        > wpp._MRHS_FULLZ_VMEM_CAP
    u_here, u_bw, psi_b = _eo_mrhs_problem(dims, 0, 2, jnp.float32)
    want = jax.vmap(lambda p: wpp.dslash_eo_pallas_packed(
        u_here, u_bw, p, dims, 0, interpret=True))(psi_b)
    got = wpp.dslash_eo_pallas_packed_mrhs(
        u_here, u_bw, psi_b, dims, 0, interpret=True)
    assert bool(jnp.all(got == want))
    assert route_counts() == {"zblock": 1.0}


def _one_slice(monkeypatch, wpp, dims, dtype):
    # a cap that one time-slice a step passes with the xc block and two
    # do not
    T, Z, Y, X = dims
    one, two = (wpp._mrhs_fullz_vmem(Z, Y * X // 2, dtype, dtype, 3, bt,
                                     dtype)[1] for bt in (1, 2))
    monkeypatch.setattr(wpp, "_MRHS_FULLZ_VMEM_CAP", (one + two) // 2)


def _no_room_for_xc(monkeypatch, wpp, dims, dtype):
    # no full-Z tiles, and a z-block budget that the hop's 288 planes
    # pass and the 312 with the xc block do not
    monkeypatch.setattr(wpp, "_MRHS_FULLZ_VMEM_CAP", 0)
    plane = wpp._sublane_rows(dtype) * 128 * jnp.dtype(dtype).itemsize
    monkeypatch.setenv("QUDA_TPU_PALLAS_VMEM_MB",
                       str(300 * plane / 2 ** 20))


# route -> (what bends the shapes' own choice, the route counted, the
# epilogue counted).  A bent call has a batch of its own size: the cap
# and the knob are no part of the jitted call's key.
_COMBINE_ROUTES = {"fullz2": (None, "fullz", "combine"),
                   "fullz1": (_one_slice, "fullz", "combine"),
                   "zblock": (None, "zblock", "combine"),
                   "xla": (_no_room_for_xc, "zblock", "none")}


def _epilogue_case(route, parity, dtype, monkeypatch):
    """One of ``_COMBINE_ROUTES`` bent into place and a problem on it:
    (dims, nrhs, the call's keywords, links, backward links, psi, xc,
    the route counted, the epilogue counted)."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    bend, counted, epilogue = _COMBINE_ROUTES[route]
    dims, nrhs = _fz_dims(dtype), 2 if bend is None else 3
    kw = ({"block_z": wpp._sublane_rows(dtype)} if route == "zblock"
          else {})
    if bend is not None:
        bend(monkeypatch, wpp, dims, dtype)
    u_here, u_bw, psi_b = _eo_mrhs_problem(dims, parity, nrhs, dtype)
    xc = _eo_mrhs_problem(dims, parity, nrhs, dtype, seed=9)[2]
    return dims, nrhs, kw, u_here, u_bw, psi_b, xc, counted, epilogue


@pytest.mark.parametrize("parity,g5,route,dtype", [
    (0, True, "fullz2", jnp.float32),
    pytest.param(1, False, "zblock", jnp.float32, marks=pytest.mark.slow),
    pytest.param(1, True, "fullz1", jnp.float32, marks=pytest.mark.slow),
    pytest.param(0, True, "xla", jnp.float32, marks=pytest.mark.slow),
    pytest.param(1, True, "fullz2", jnp.bfloat16, marks=pytest.mark.slow),
    pytest.param(0, False, "fullz1", jnp.bfloat16,
                 marks=pytest.mark.slow),
    pytest.param(0, True, "zblock", jnp.bfloat16,
                 marks=pytest.mark.slow),
    # every route in tier 1 since the epilogue also sums (PR 37), with
    # and without g5
    (1, False, "fullz1", jnp.float32),
    (1, True, "zblock", jnp.float32),
    (0, False, "xla", jnp.float32)])
def test_mrhs_combine_epilogue_is_xla_on_the_plain_hop(
        parity, g5, route, dtype, route_counts, monkeypatch):
    """``xc``, ``coeff``, ``g5``: the call writes ``[g5] (xc + coeff *
    hop)`` from its f32 accumulators, rounded to the storage dtype once,
    on either route, and is counted with ``epilogue="combine"``; where
    no route holds the xc block (``xla``) XLA combines the bare hop, as
    without the epilogue.  Against the plain call's f32 hop combined by
    XLA: the last bit may differ (one contracts the multiply-add, one
    does not), and with it now and then the bf16 a sum rounds to.
    Besides, the call returns the per-source sums of squares of what
    it stored (f32, to f32 rounding of a sum taken in f64: the kernel's
    own partial sums, XLA's on the fallback), counted with
    ``reduce="norm2"``."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    (dims, nrhs, kw, u_here, u_bw, psi_b, xc, counted,
     epilogue) = _epilogue_case(route, parity, dtype, monkeypatch)
    coeff = -0.12 ** 2
    got, sums = wpp.dslash_eo_pallas_packed_mrhs_combine(
        u_here, u_bw, psi_b, dims, parity, interpret=True, xc=xc,
        coeff=coeff, g5=g5, **kw)
    assert sums.shape == (nrhs,) and sums.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(sums), np.asarray(jnp.sum(
            got.astype(jnp.float64).reshape(nrhs, -1) ** 2, axis=1)),
        rtol=2e-6)
    hop = wpp.dslash_eo_pallas_packed_mrhs(
        u_here, u_bw, psi_b, dims, parity, interpret=True,
        out_dtype=jnp.float32, **kw)
    want = xc.astype(jnp.float32) + jnp.float32(coeff) * hop
    if g5:
        want = want * jnp.asarray([1, 1, -1, -1], jnp.float32).reshape(
            1, 4, 1, 1, 1, 1, 1)
    want = want.astype(dtype).astype(jnp.float32)
    assert got.dtype == dtype and got.shape == psi_b.shape
    diff = jnp.abs(got.astype(jnp.float32) - want)
    ulp = float(jnp.finfo(dtype).eps) * float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(diff)) <= ulp
    if dtype == jnp.bfloat16:
        assert float(jnp.mean(diff == 0)) > 0.99
    assert route_counts() == {counted: 2.0}
    assert route_counts("epilogue") == (
        {"none": 2.0} if epilogue == "none"
        else {"none": 1.0, "combine": 1.0})
    # the fallback's sums are XLA's: no kernel is counted with them
    assert route_counts("reduce") == (
        {"none": 2.0} if epilogue == "none"
        else {"none": 1.0, "norm2": 1.0})


def test_mrhs_kernel_is_traced_from_a_frame_too_large_for_a_chunk():
    """The MRHS ``pallas_call`` is made from a frame that no 16 KiB
    chunk of CPython's frame stack has room left for, so that the
    kernel body's trace does not straddle a chunk boundary by the luck
    of its caller's depth (PERF.md section 7 (22)); it hands through
    what it calls."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    call = wpp._on_a_stack_chunk_of_its_own
    assert call.__code__.co_nlocals * 8 > 2 * 16 * 1024
    assert call(lambda: sys._getframe(1).f_code) is call.__code__


@pytest.mark.parametrize("parity,g5,route,dtype", [
    (0, True, "fullz2", jnp.float32),
    (1, True, "xla", jnp.float32),
    pytest.param(1, False, "fullz1", jnp.float32, marks=pytest.mark.slow),
    pytest.param(0, True, "zblock", jnp.float32, marks=pytest.mark.slow),
    pytest.param(1, True, "fullz2", jnp.bfloat16, marks=pytest.mark.slow)])
def test_mrhs_residual_epilogue_is_xla_on_the_combine_hop(
        parity, g5, route, dtype, route_counts, monkeypatch):
    """``rc`` and ``alpha`` besides ``xc`` and ``coeff``: the call
    writes ``rc - alpha[n] * [g5] (xc + coeff * hop)``, source n with
    ITS alpha (every source has another: a swapped index is far off),
    rounded to the storage dtype once, and returns the per-source sums
    of squares of what it stored; counted with ``epilogue="residual"``.
    Where no route holds the two blocks (``xla``) it is the combine
    call (here in turn the bare hop and XLA's combine) and XLA's
    update and sum.  Against the combine call's f32 result updated by
    XLA."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    (dims, nrhs, kw, u_here, u_bw, psi_b, xc, counted,
     epilogue) = _epilogue_case(route, parity, dtype, monkeypatch)
    rc = _eo_mrhs_problem(dims, parity, nrhs, dtype, seed=10)[2]
    coeff = -0.12 ** 2
    alpha = jnp.asarray([0.37, -1.9, 2.6][:nrhs], jnp.float32)
    got, sums = wpp.dslash_eo_pallas_packed_mrhs_residual(
        u_here, u_bw, psi_b, dims, parity, interpret=True, xc=xc,
        coeff=coeff, g5=g5, rc=rc, alpha=alpha, **kw)
    assert sums.shape == (nrhs,) and sums.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(sums), np.asarray(jnp.sum(
            got.astype(jnp.float64).reshape(nrhs, -1) ** 2, axis=1)),
        rtol=2e-6)
    v = wpp.dslash_eo_pallas_packed_mrhs_combine(
        u_here, u_bw, psi_b, dims, parity, interpret=True, xc=xc,
        coeff=coeff, g5=g5, out_dtype=jnp.float32, **kw)[0]

    def update(a):
        w = rc.astype(jnp.float32) - a.reshape((nrhs,) + (1,) * 6) * v
        return w.astype(dtype).astype(jnp.float32)
    want = update(alpha)
    assert got.dtype == dtype and got.shape == psi_b.shape
    diff = jnp.abs(got.astype(jnp.float32) - want)
    ulp = float(jnp.finfo(dtype).eps) * float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(diff)) <= ulp
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - update(alpha[::-1])))) > 1e3 * ulp
    assert route_counts() == {counted: 2.0}
    assert route_counts("epilogue") == (
        {"none": 2.0} if epilogue == "none"
        else {"combine": 1.0, "residual": 1.0})
    assert route_counts("reduce") == (
        {"none": 2.0} if epilogue == "none" else {"norm2": 2.0})
    with pytest.raises(ValueError, match="mrhs_residual"):
        wpp.dslash_eo_pallas_packed_mrhs_combine(
            u_here, u_bw, psi_b, dims, parity, interpret=True, xc=xc,
            coeff=coeff, rc=rc, alpha=alpha)


@pytest.mark.parametrize("case,want", [
    # (T, Z, YX, storage, out, link rows R, block_z[, xc storage
    # [, rc storage]]) -> (route, bz, bt)
    ((24, 24, 288, jnp.float32, jnp.float32, 3, None), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.bfloat16, jnp.bfloat16, 3, None), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.bfloat16, jnp.float32, 3, None), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.float32, jnp.float32, 2, None), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.float32, jnp.float32, 3, 24), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.float32, jnp.float32, 3, 8), ("zblock", 8, 1)),
    ((9, 24, 288, jnp.float32, jnp.float32, 3, None), ("fullz", 24, 1)),
    ((32, 32, 512, jnp.float32, jnp.float32, 3, None), ("fullz", 32, 1)),
    ((32, 32, 512, jnp.bfloat16, jnp.bfloat16, 3, None), ("fullz", 32, 2)),
    ((40, 40, 640, jnp.float32, jnp.float32, 3, None), ("zblock", 8, 1)),
    ((8, 8, 8, jnp.float32, jnp.float32, 3, None), ("fullz", 8, 2)),
    # the combine epilogue's xc block is one more operand, and its
    # block of sums one chunk of f32 rows more: 24^4 still takes two
    # slices a step (38.8 MiB of the 48), a 32 x 32 plane at
    # Z = 24 takes two without it and one with it, and where the hop's
    # own z-block is the largest that fits no route holds it
    ((24, 24, 288, jnp.float32, jnp.float32, 3, None, jnp.float32),
     ("fullz", 24, 2)),
    ((24, 24, 288, jnp.bfloat16, jnp.bfloat16, 3, None, jnp.bfloat16),
     ("fullz", 24, 2)),
    ((24, 24, 512, jnp.float32, jnp.float32, 3, None), ("fullz", 24, 2)),
    ((24, 24, 512, jnp.float32, jnp.float32, 3, None, jnp.float32),
     ("fullz", 24, 1)),
    ((24, 24, 288, jnp.float32, jnp.float32, 3, 8, jnp.float32),
     ("zblock", 8, 1)),
    ((40, 40, 640, jnp.float32, jnp.float32, 3, None, jnp.float32),
     ValueError),
    # the residual form's rc block is one more again: 24^4 still takes
    # two slices a step (42.2 MiB of the 48), eight rows of 1,408
    # lanes take two with the xc block and one with both
    ((24, 24, 288, jnp.float32, jnp.float32, 3, None, jnp.float32,
      jnp.float32), ("fullz", 24, 2)),
    ((24, 24, 288, jnp.bfloat16, jnp.bfloat16, 3, None, jnp.bfloat16,
      jnp.bfloat16), ("fullz", 24, 2)),
    ((24, 8, 1408, jnp.float32, jnp.float32, 3, None, jnp.float32),
     ("fullz", 8, 2)),
    ((24, 8, 1408, jnp.float32, jnp.float32, 3, None, jnp.float32,
      jnp.float32), ("fullz", 8, 1)),
    ((24, 24, 288, jnp.float32, jnp.float32, 3, 8, jnp.float32,
      jnp.float32), ("zblock", 8, 1)),
    ((40, 40, 640, jnp.float32, jnp.float32, 3, None, jnp.float32,
      jnp.float32), ValueError),
    # the shapes the interpreted cases of this file run on
    # (``_fz_dims``, the z-block case, the epilogues' bent ``zblock``)
    ((4, 16, 4, jnp.float32, jnp.float32, 3, None), ("fullz", 16, 2)),
    ((4, 32, 4, jnp.bfloat16, jnp.bfloat16, 3, None), ("fullz", 32, 2)),
    ((2, 40, 640, jnp.float32, jnp.float32, 3, None), ("zblock", 8, 1)),
    ((4, 16, 4, jnp.float32, jnp.float32, 3, 8, jnp.float32),
     ("zblock", 8, 1))])
def test_mrhs_route_follows_the_shapes(case, want, route_counts):
    """The route is arithmetic on (T, Z, YX, dtypes, R): padded planes x
    bytes, twice for the pipeline's buffers, plus the body's tiles,
    against the limit the call sets: two time-slices a step where they
    fit and T is even, one where only that fits, z-blocks where neither
    does; a caller's ``block_z`` wins.  The full-Z call's
    ``vmem_limit_bytes`` holds what it computed, and the VMEM audit
    keeps the route's blocks beside the knob's own row."""
    from quda_tpu.obs import memory as omem
    from quda_tpu.ops import wilson_pallas_packed as wpp
    T, Z, YX, dt, odt, R, block_z, xc_dt, rc_dt = (case + (None,) * 2)[:9]
    if want is ValueError:
        with pytest.raises(ValueError, match="fits the VMEM budget"):
            wpp._mrhs_route(T, Z, YX, dt, odt, R, block_z, xc_dt, rc_dt)
        assert route_counts() == {}
        return
    route, bz, bt, limit = wpp._mrhs_route(T, Z, YX, dt, odt, R, block_z,
                                           xc_dt, rc_dt)
    rows = {r["knob"]: r for r in omem.audit_vmem_budgets()}
    assert (route, bz, bt) == want and route_counts() == {route: 1.0}
    assert route_counts("epilogue") == {
        "none" if xc_dt is None else
        "combine" if rc_dt is None else "residual": 1.0}
    assert route_counts("reduce") == {
        "none" if xc_dt is None else "norm2": 1.0}
    blocks, need = wpp._mrhs_fullz_vmem(Z, YX, dt, odt, R, bt, xc_dt,
                                        rc_dt)
    if rc_dt is not None:
        # bt spinor tiles more than with the xc block alone
        with_xc = wpp._mrhs_fullz_vmem(Z, YX, dt, odt, R, bt, xc_dt)[0]
        sub = wpp._sublane_rows(rc_dt)
        assert blocks - with_xc == (
            bt * 24 * -(-Z // sub) * sub * -(-YX // 128) * 128
            * jnp.dtype(rc_dt).itemsize)
    elif xc_dt is not None:
        # bt spinor tiles and one chunk of the body's rows in f32 (the
        # epilogue's sums), every plane padded to 128 lanes
        def padded(n, d):
            sub = wpp._sublane_rows(d)
            return -(-n // sub) * sub
        lanes = -(-YX // 128) * 128
        assert blocks - wpp._mrhs_fullz_vmem(Z, YX, dt, odt, R, bt)[0] == (
            bt * 24 * padded(Z, xc_dt) * lanes * jnp.dtype(xc_dt).itemsize
            + padded(wpp._fullz_chunk(Z, dt), jnp.float32) * lanes * 4)
    if route == "fullz":
        assert need <= limit <= wpp._MRHS_FULLZ_VMEM_CAP
        row = rows["QUDA_TPU_PALLAS_VMEM_MB[fullz]"]
        assert row["last_bz"] == Z and row["last_block_bytes"] == blocks
        assert row["double_buffer_ok"]
    else:
        assert limit is None
        assert "QUDA_TPU_PALLAS_VMEM_MB[fullz]" not in rows


# -- round 10: staggered MRHS (the second headline family) ------------------

@pytest.mark.slow
@pytest.mark.parametrize("nrhs", [1, 3, 8])
def test_staggered_mrhs_kernel_bitmatches_vmapped(nrhs):
    """dslash_staggered_pallas_mrhs bit-matches jax.vmap of the
    single-RHS two-pass kernel for N in {1, 3, 8} (fat + Naik; the
    fat/long tiles are fetched once per (t, z-block) for all N)."""
    from quda_tpu.ops import staggered_pallas as stp
    rng = np.random.default_rng(9)
    fat = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * KX)), jnp.float32)
    lng = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * KX)), jnp.float32)
    psi_b = jnp.asarray(rng.standard_normal(
        (nrhs, 3, 2, KT, KZ, KY * KX)), jnp.float32)
    fat_bw = stp.backward_links(fat, KX, 1)
    long_bw = stp.backward_links(lng, KX, 3)
    want = jax.vmap(lambda p: stp.dslash_staggered_pallas(
        fat, fat_bw, p, KX, long_pl=lng, long_bw_pl=long_bw,
        interpret=True))(psi_b)
    got = stp.dslash_staggered_pallas_mrhs(
        fat, fat_bw, psi_b, KX, long_pl=lng, long_bw_pl=long_bw,
        interpret=True)
    assert bool(jnp.all(got == want))


@pytest.mark.slow
@pytest.mark.parametrize("parity", [0, 1])
def test_staggered_mrhs_eo_kernel_bitmatches_all_parities(parity):
    """The eo staggered MRHS kernel (the batched staggered solver hot
    path) bit-matches the single-RHS eo kernel on both target parities,
    including the degenerate N=1."""
    from quda_tpu.ops import staggered_pallas as stp
    dims = (KT, KZ, KY, KX)
    Xh = KX // 2
    rng = np.random.default_rng(10)
    fat_here = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    fat_there = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    lng_here = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    lng_there = jnp.asarray(rng.standard_normal(
        (4, 3, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
    fat_bw = stp.backward_links_eo(fat_there, dims, parity, 1)
    long_bw = stp.backward_links_eo(lng_there, dims, parity, 3)
    for nrhs in (1, 3):
        psi_b = jnp.asarray(rng.standard_normal(
            (nrhs, 3, 2, KT, KZ, KY * Xh)), jnp.float32)
        want = jnp.stack([stp.dslash_staggered_eo_pallas(
            fat_here, fat_bw, psi_b[i], dims, parity,
            long_here_pl=lng_here, long_bw_pl=long_bw, interpret=True)
            for i in range(nrhs)])
        got = stp.dslash_staggered_eo_pallas_mrhs(
            fat_here, fat_bw, psi_b, dims, parity,
            long_here_pl=lng_here, long_bw_pl=long_bw, interpret=True)
        assert bool(jnp.all(got == want)), (parity, nrhs)
