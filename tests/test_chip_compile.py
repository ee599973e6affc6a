"""AOT compile of the solve path's pallas kernels for a DESCRIBED v5e.

No chip is attached here: ``topologies.get_topology_desc`` hands the
installed TPU compiler a described v5e:2x2 and each kernel is lowered
and compiled for its first device at the production 24^4 shapes
(/opt/skills/guides/on-chip-measurement, section 2).  What the chip's
compiler refuses (tiling, VMEM, legalisation) it refuses here, at no
chip time.  Nothing executes — a pass is not a chip run.

Rules this file keeps (several xdist workers import it; only the one
that RUNS it may load libtpu): the topology call lives in a
module-scoped fixture, never at import / in a skipif / in parametrize
arguments; every compile happens in the test's own process with x64
OFF (conftest turns it on; under x64 Mosaic refuses the index maps'
i64 returns and the dslash lowering recurses out) and the persistent
compile cache OFF (an AOT entry cannot be read back without a chip).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

L = 24
DIMS = (L, L, L, L)
YXH = L * L // 2
F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _links(dt, rows=3):
    return ((4, rows, 3, 2, L, L, YXH), dt)


def _psi(dt, lead=()):
    return (tuple(lead) + (4, 3, 2, L, L, YXH), dt)


def _eo(dt, rows=3):
    from quda_tpu.ops import wilson_pallas_packed as wpp
    return (lambda u, ub, p: wpp.dslash_eo_pallas_packed(
                u, ub, p, DIMS, 0),
            [_links(dt, rows), _links(dt, rows), _psi(dt)])


def _eo_mrhs(n, dt=F32, block_z=None, combine=False, lat=L, bt=2,
             residual=False):
    """The MRHS kernel as the shapes route it: full-Z tiles at 24^4
    (three psi operands, two time-slices a step, its own
    ``vmem_limit_bytes``; 24 rows of bf16 pad to 32 sublanes, so that
    VMEM sum is another), one slice a step at 32^4; ``block_z = 8``
    keeps the z-blocked fallback compiled for the chip.  ``combine``:
    with its combine epilogue (the second hop of the batched PC
    operator: one more spinor block, the coefficient in SMEM, gamma5 in
    registers, and the per-source sums of squares of what it stores,
    the batched CG's ``pAp``: a second, small f32 output block counted
    in the route's VMEM sum), on each of those routes.  ``residual``:
    that epilogue's residual form (the last hop of a batched CG
    iteration: the ``rc`` block and one ``alpha`` a source in SMEM
    besides, the result over ``rc``'s buffer)."""
    from quda_tpu.ops import wilson_pallas_packed as wpp
    dims, yxh = (lat,) * 4, lat * lat // 2
    want = ("zblock", block_z, 1) if block_z else ("fullz", lat, bt)
    epilogue = (dt if combine or residual else None,
                dt if residual else None)
    route = wpp._mrhs_route(lat, lat, yxh, dt, dt, 3, block_z, *epilogue)
    assert route[:3] == want
    if not block_z:
        assert wpp._mrhs_fullz_vmem(lat, yxh, dt, dt, 3, bt, *epilogue
                                    )[1] <= route[3] <= wpp._MRHS_FULLZ_VMEM_CAP
    links = ((4, 3, 3, 2, lat, lat, yxh), dt)
    psi = ((n, 4, 3, 2, lat, lat, yxh), dt)
    if residual:
        return (lambda u, ub, p, xc, k, rc, a:
                wpp.dslash_eo_pallas_packed_mrhs_residual(
                    u, ub, p, dims, 0, block_z=block_z, xc=xc, coeff=k,
                    g5=True, rc=rc, alpha=a),
                [links, links, psi, psi, ((), F32), psi, ((n,), F32)])
    if not combine:
        return (lambda u, ub, p: wpp.dslash_eo_pallas_packed_mrhs(
                    u, ub, p, dims, 0, block_z=block_z),
                [links, links, psi])
    return (lambda u, ub, p, xc, k: wpp.dslash_eo_pallas_packed_mrhs_combine(
                u, ub, p, dims, 0, block_z=block_z, xc=xc, coeff=k,
                g5=True),
            [links, links, psi, psi, ((), F32)])


def _multishift_update(n, field):
    """The multi-shift loop's update of its live shifts on a stack of
    ``n`` pair fields (solvers/multishift.update_form takes it for
    any real source on the chip): the staggered colour vector of the
    cell and the Wilson spinor of the eager route."""
    from quda_tpu.ops import blas_pallas as bp
    coef = ((n,), F32)
    return (bp.multishift_update_pallas,
            [((), jnp.int32), coef, coef, coef, ((n,) + field, F32),
             ((n,) + field, F32), (field, F32)])


def _staggered_eo_v3(dt):
    """The served single-source fat + Naik hop at 24^4
    (models/staggered.served_forms): bf16 in the mixed CG's loop, f32
    in the multi-shift loop and at every exit."""
    from quda_tpu.ops import staggered_pallas as sp
    lk = ((4, 3, 3, 2, L, L, YXH), dt)
    return (lambda fh, ft, p, lh, lt: sp.dslash_staggered_eo_pallas_v3(
                fh, ft, p, DIMS, 0, long_here_pl=lh, long_there_pl=lt),
            [lk, lk, ((3, 2, L, L, YXH), dt), lk, lk])


def _staggered_eo_mrhs(form, parity, n=8):
    """The batched fat + Naik hop at 24^4, N sources, f32: the served
    scatter form (models/staggered.served_forms) and the gather
    form it was read against; the fourth and fifth operand are the
    other parity's links (scatter) or the pre-shifted backward links
    (gather)."""
    from quda_tpu.ops import staggered_pallas as sp
    lk = ((4, 3, 3, 2, L, L, YXH), F32)
    if form == "scatter":
        fn = lambda fh, ft, p, lh, lt: sp.dslash_staggered_eo_pallas_v3_mrhs(
            fh, ft, p, DIMS, parity, long_here_pl=lh, long_there_pl=lt)
    else:
        fn = lambda fh, fb, p, lh, lb: sp.dslash_staggered_eo_pallas_mrhs(
            fh, fb, p, DIMS, parity, long_here_pl=lh, long_bw_pl=lb)
    return fn, [lk, lk, ((n, 3, 2, L, L, YXH), F32), lk, lk]


def _clover_pc_k1():
    from quda_tpu.ops import clover_pallas as cp
    blk = ((2, 6, 6, 2, L, L, YXH), F32)
    return (lambda u, ub, p, b: cp.dslash_eo_pallas_post(
                u, ub, p, DIMS, 0, blk_pl=b),
            [_links(F32), _links(F32), _psi(F32), blk])


def _clover_pc_k2_residual_bf16():
    """The sloppy K2 call as the last kernel of a mixed-precision CG
    iteration (PR 50): bf16 links, blocks and spinors, gamma5 in the
    store, ``rc`` and one ``alpha`` besides, the hop sum in an f32 VMEM
    scratch under the bf16 out tile, the new ``r`` over the old and
    its sum of squares a second result; z-blocks of 8 rows under
    Mosaic's default scoped limit (no ``vmem_limit_bytes``)."""
    from quda_tpu.ops import clover_pallas as cp
    from quda_tpu.ops import wilson_pallas_packed as wpp
    assert wpp._pick_bz(L, YXH, BF16, planes=cp._planes(
        3, "input", True, True)) == 8
    blk = ((2, 6, 6, 2, L, L, YXH), BF16)
    return (lambda u, ub, p, x, k, b, r, a: cp.dslash_eo_pallas_diag_hop(
                u, ub, p, x, DIMS, 0, hop_coeff=k, blk_pl=b, g5=True,
                rc=r, alpha=a),
            [_links(BF16), _links(BF16), _psi(BF16), _psi(BF16),
             ((), F32), blk, _psi(BF16), ((), F32)])


def _clover_mrhs(stage, n=8, block_z=None, lat=L):
    """A fused clover MRHS kernel as the shapes route it (PR 47): at
    24^4 with the chiral blocks full-Z tiles, one time-slice a step,
    three spinor operands (and ``xc`` for ``diag_hop``) and the
    ``vmem_limit_bytes`` of ops/clover_pallas.mrhs_route (32.1 / 33.8
    MiB); ``block_z = 8`` keeps the five-operand z-blocked fallback of
    larger local volumes compiled for the chip.  ``residual`` (PR 48):
    the K2 call as the last kernel of a batched CG iteration, gamma5 in
    the store, ``rc`` and the per-source ``alpha`` besides, the new
    ``r`` over the old and its sums of squares a second result: ten
    operands, still ``fullz`` with one slice a step, 35.4 of the 48
    MiB."""
    from quda_tpu.ops import clover_pallas as cp
    from quda_tpu.ops import wilson_pallas_packed as wpp
    dims, yxh = (lat,) * 4, lat * lat // 2
    links = ((4, 3, 3, 2, lat, lat, yxh), F32)
    psi = ((n, 4, 3, 2, lat, lat, yxh), F32)
    blk = ((2, 6, 6, 2, lat, lat, yxh), F32)
    u, p, b = (jax.ShapeDtypeStruct(*v) for v in (links, psi, blk))
    route = cp.mrhs_route(u, p, None if stage == "post" else p, b, F32,
                          block_z, p if stage == "residual" else None)
    want = ("zblock", block_z, 1, None) if block_z else (
        "fullz", lat, 1, {"post": 33619968, "diag_hop": 35389440,
                          "residual": 37158912}[stage])
    assert route == want, route
    assert block_z or route[3] <= wpp._MRHS_FULLZ_VMEM_CAP == 48 * 2 ** 20
    if stage == "residual":
        return (lambda u, ub, p, x, k, b, r, a:
                cp.dslash_eo_pallas_diag_hop_mrhs(
                    u, ub, p, x, dims, 0, hop_coeff=k, blk_pl=b,
                    block_z=block_z, out_dtype=F32, g5=True, rc=r, alpha=a),
                [links, links, psi, psi, ((), F32), blk, psi, ((n,), F32)])
    if stage == "post":
        return (lambda u, ub, p, b: cp.dslash_eo_pallas_post_mrhs(
                    u, ub, p, dims, 1, blk_pl=b, block_z=block_z),
                [links, links, psi, blk])
    return (lambda u, ub, p, x, k, b: cp.dslash_eo_pallas_diag_hop_mrhs(
                u, ub, p, x, dims, 0, hop_coeff=k, blk_pl=b,
                block_z=block_z, out_dtype=F32),
            [links, links, psi, psi, ((), F32), blk])


def _dwf_ls8():
    from quda_tpu.ops import dwf_pallas as dp
    return (lambda u, ub, p: dp.dslash_eo_pallas_packed_ls(
                u, ub, p, DIMS, 0),
            [_links(F32), _links(F32), _psi(F32, (8,))])


def _mobius_sblock(dt, axpy=False):
    """The (Ls, Ls) chirality blocks of the Möbius cell on a 24^4 x 12
    pair array (ops/dwf_pallas): the plain product at the loop's and
    the exit's widths, the accumulate form ``y - 1/4 B x`` in f32."""
    from quda_tpu.ops import dwf_pallas as dp
    v = _psi(dt, (MOBIUS_LS,))
    blocks = ((2, MOBIUS_LS, MOBIUS_LS), F32)
    if axpy:
        return (lambda x, y, b: dp.mobius_sblock_axpy_pallas(x, y, -0.25, b),
                [v, v, blocks])
    return (dp.mobius_sblock_pallas, [v, blocks])


def _coarse():
    # 24^4 fine lattice, 4^4 blocks -> 6^4 = 1296 coarse sites, 24 null
    # vectors -> E = 2*Nc = 48 (the shape bench_mg_scale builds)
    from quda_tpu.ops import coarse_pallas as cop
    return (cop.coarse_apply_pallas,
            [((9, 1296, 48, 48), F32), ((9, 1296, 48), F32)])


CASES = {
    # the Wilson main path (invert_quda / invert_multi_src_quda / serve)
    "wilson_eo_v2_f32": lambda: _eo(F32),
    "wilson_eo_v2_bf16": lambda: _eo(BF16),
    "wilson_eo_v2_recon12": lambda: _eo(F32, rows=2),
    "wilson_eo_mrhs_n8": lambda: _eo_mrhs(8),
    "wilson_eo_mrhs_n8_bf16": lambda: _eo_mrhs(8, BF16),
    "wilson_eo_mrhs_n8_zblock": lambda: _eo_mrhs(8, block_z=8),
    "wilson_eo_mrhs_n8_combine": lambda: _eo_mrhs(8, combine=True),
    "wilson_eo_mrhs_n8_combine_bf16": lambda: _eo_mrhs(
        8, BF16, combine=True),
    "wilson_eo_mrhs_n8_combine_32": lambda: _eo_mrhs(
        8, combine=True, lat=32, bt=1),
    "wilson_eo_mrhs_n8_combine_zblock": lambda: _eo_mrhs(
        8, block_z=8, combine=True),
    "wilson_eo_mrhs_n8_residual": lambda: _eo_mrhs(8, residual=True),
    "wilson_eo_mrhs_n8_residual_32": lambda: _eo_mrhs(
        8, residual=True, lat=32, bt=1),
    "wilson_eo_mrhs_n8_residual_zblock": lambda: _eo_mrhs(
        8, block_z=8, residual=True),
    "multishift_update_n14_staggered": lambda: _multishift_update(
        14, (3, 2, L, L, YXH)),
    "multishift_update_n4_wilson": lambda: _multishift_update(
        4, (4, 3, 2, L, L, YXH)),
    "multishift_update_n14_wilson": lambda: _multishift_update(
        14, (4, 3, 2, L, L, YXH)),
    # one case per other operator family the solve API routes to a kernel
    "staggered_eo_v3_f32": lambda: _staggered_eo_v3(F32),
    "staggered_eo_v3_bf16": lambda: _staggered_eo_v3(BF16),
    "staggered_eo_mrhs_n8_scatter_even": lambda: _staggered_eo_mrhs(
        "scatter", 0),
    "staggered_eo_mrhs_n8_scatter_odd": lambda: _staggered_eo_mrhs(
        "scatter", 1),
    "staggered_eo_mrhs_n8_gather_even": lambda: _staggered_eo_mrhs(
        "gather", 0),
    "staggered_eo_mrhs_n8_gather_odd": lambda: _staggered_eo_mrhs(
        "gather", 1),
    "clover_pc_k1": _clover_pc_k1,
    "clover_pc_k2_residual_bf16": _clover_pc_k2_residual_bf16,
    "clover_mrhs_n8_post": lambda: _clover_mrhs("post"),
    "clover_mrhs_n8_diag_hop": lambda: _clover_mrhs("diag_hop"),
    "clover_mrhs_n8_diag_hop_zblock": lambda: _clover_mrhs(
        "diag_hop", block_z=8),
    "clover_mrhs_n8_diag_hop_residual": lambda: _clover_mrhs("residual"),
    "dwf_eo_ls8": _dwf_ls8,
    "mobius_sblock_ls12_bf16": lambda: _mobius_sblock(BF16),
    "mobius_sblock_ls12_f32": lambda: _mobius_sblock(F32),
    "mobius_sblock_axpy_ls12_f32": lambda: _mobius_sblock(F32, axpy=True),
    "mg_coarse_1296x48": _coarse,
}


# Refused by the chip's compiler and NOT on the Wilson main path: kept
# as strict xfails (an unexpected pass fails, so the repair is noticed).
# With _pick_bs's 27-site block the refusal was the sublane tiling of
# the site axis; with the admissible 24-site block Mosaic gets as far
# as the contraction itself.
REFUSED = {
    "mg_coarse_1296x48": (
        "Mosaic refuses the kernel's 'ksab,ksb->sa' contraction: "
        "'tpu.matmul' op Not implemented: lhs contracting dims must be "
        "of size 1 (two contracted axes k,b in one dot_general)"),
}


def _case_params():
    return [pytest.param(c, marks=pytest.mark.xfail(
                strict=True, reason=REFUSED[c])) if c in REFUSED
            else c for c in sorted(CASES)]


@pytest.mark.parametrize("case", _case_params())
def test_kernel_compiles_for_v5e(case, one_chip):
    from jax.experimental.compilation_cache import compilation_cache as cc
    fn, shapes = CASES[case]()
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                    for s, d in shapes]
            compiled = jax.jit(fn).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
    assert "tpu_custom_call" in compiled.as_text()


_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
              "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
              "u64": 8, "c64": 8, "c128": 16}


def _hlo_values(hlo, op):
    """(bytes, dtype, dims) of every ``op(`` instruction of an HLO text."""
    import math
    import re
    pat = re.compile(r"= (\w+)\[([\d,]*)\](?:\{[^}]*\})? " + op + r"\(")
    return [(_HLO_BYTES[dt] * math.prod(int(d) for d in dims.split(",")
                                        if d), dt, dims)
            for dt, dims in pat.findall(hlo)]


def _hlo_computation(hlo, name):
    """The text of one computation of an HLO module, by its name."""
    start = hlo.index(f"\n%{name} (")
    return hlo[start:hlo.index("\n}\n", start)]


def test_solve_program_compiles_for_v5e_with_links_as_parameters(one_chip):
    """The single-source solve program (solvers/program.py: mixed
    f32/bf16 reliable CG, prologue + while_loop + final fold) at 24^4:
    lowers and compiles for the described chip with the resident links
    of both operators as PARAMETERS, and bakes no field into the
    executable (PR 22 cause 8: a closed-over gauge was a 353 MB
    constant)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.wilson import DiracWilsonPC
    from quda_tpu.solvers import mixed
    from quda_tpu.solvers import program as sprog
    geom = LatticeGeometry(DIMS)

    def operators(gauge):
        dpk = DiracWilsonPC(gauge, geom, 0.124).packed()
        return tuple(dpk.pairs(dt, use_pallas=True, pallas_interpret=False,
                               precision_form="full")
                     for dt in (F32, BF16))

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            ops = jax.eval_shape(operators, jax.ShapeDtypeStruct(
                (4,) + DIMS + (3, 3), jnp.complex64))
            hi, lo = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=one_chip,
                    weak_type=s.weak_type), ops)
            b = jax.ShapeDtypeStruct(*_psi(F32), sharding=one_chip)
            key = (0.1, mixed.pair_inplace_config(BF16),
                   sprog._LoopKnobs(False, None, None, None), False)
            compiled = sprog._cg_reliable_program.lower(
                hi, lo, b, 1e-6, 10000, key=key).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    params = _hlo_values(hlo, "parameter")
    links = ",".join(str(d) for d in _links(F32)[0])
    # forward + pre-shifted backward links of two parities, per operator
    assert sum(p[1:] == ("f32", links) for p in params) == 4
    assert sum(p[1:] == ("bf16", links) for p in params) == 4
    consts = _hlo_values(hlo, "constant")
    big = [c for c in consts if c[0] > 2 ** 20]
    assert consts and not big, f"fields baked into the executable: {big}"


def _aot(lower):
    """Compile for the described chip with x64 and the persistent
    cache off (module docstring); ``lower()`` returns the Lowered."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            return lower().compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()


def test_clover_term_construction_compiles_for_v5e_under_8_gib(one_chip):
    """The lattice-minor clover construction (ops/clover_packed: field
    strength, chiral blocks of both parities, the inverse) as ONE
    jitted program at 24^4 from the canonical resident gauge: it fits
    (the canonical construction's F_munu alone is 16 GB of tile-padded
    temporaries, PERF.md) and bakes no field into the executable."""
    from quda_tpu.ops import clover_packed as cpk

    def lower():
        g = jax.ShapeDtypeStruct((4,) + DIMS + (3, 3), jnp.complex64,
                                 sharding=one_chip)
        c = jax.ShapeDtypeStruct((), F32, sharding=one_chip)
        return jax.jit(lambda g, c: cpk.clover_term_packed(
            g, c, DIMS, 0)).lower(g, c)
    compiled = _aot(lower)
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert peak < 8 * 2 ** 30, ma
    big = [c for c in _hlo_values(compiled.as_text(), "constant")
           if c[0] > 2 ** 20]
    assert not big, f"fields baked into the executable: {big}"


def test_clover_solve_program_compiles_for_v5e_with_blocks_as_parameters(
        one_chip):
    """The clover single-source solve program (solvers/program.py on
    DiracCloverPCPairs, fused form) at 24^4: compiles for the described
    chip with the links AND the clover blocks of both operators as
    parameters (PR 22 cause 8: nothing the size of a field is a
    constant), and both fused kernels are there in f32 and in bf16."""
    import re
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.clover import DiracCloverPCPairs
    from quda_tpu.solvers import mixed
    from quda_tpu.solvers import program as sprog
    geom = LatticeGeometry(DIMS)
    half = (L, L, YXH)

    def operators(links_e, links_o, a_p, ainv_q):
        return tuple(DiracCloverPCPairs.from_packed(
            geom, (links_e, links_o), 0.124, 0, a_p, ainv_q, dt,
            use_pallas=True, pallas_interpret=False,
            form="pallas") for dt in (F32, BF16))

    def lower():
        lk = jax.ShapeDtypeStruct((4, 3, 3) + half, jnp.complex64)
        bk = jax.ShapeDtypeStruct((2, 6, 6) + half, jnp.complex64)
        ops = jax.eval_shape(operators, lk, lk, bk, bk)
        hi, lo = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one_chip,
                weak_type=s.weak_type), ops)
        b = jax.ShapeDtypeStruct(*_psi(F32), sharding=one_chip)
        key = (0.1, mixed.pair_inplace_config(BF16),
               sprog._LoopKnobs(False, None, None, None), False)
        return sprog._cg_reliable_program.lower(hi, lo, b, 1e-6, 10000,
                                                key=key)
    hlo = _aot(lower).as_text()
    # every M of the loop is one post + one diag_hop kernel, and each
    # result carries its operator's storage type: the sloppy diag_hop
    # rounds in its store (PR 50: the loop's step is the operator's,
    # its two bf16 diag_hop calls the norm2 and the residual form, the
    # spinor and its f32 sums a tuple)
    calls = re.findall(r"%(dslash_eo_pallas_post|dslash_eo_pallas_diag_hop)"
                       r"[.\d]* = (\(?)(\w+)\[[^\n]*tpu_custom_call", hlo)
    post = [dt for k, _, dt in calls if k == "dslash_eo_pallas_post"]
    assert set(post) == {"f32", "bf16"}, calls
    assert len(calls) == 2 * len(post) and post.count("bf16") == 2
    assert sorted((tup, dt) for k, tup, dt in calls
                  if k == "dslash_eo_pallas_diag_hop") == sorted(
        [("(", "bf16")] * 2 + [("", "f32")] * (len(post) - 2)), calls
    params = _hlo_values(hlo, "parameter")
    links = ",".join(str(d) for d in _links(F32)[0])
    blocks = ",".join(str(d) for d in (2, 6, 6, 2, L, L, YXH))
    for dt in ("f32", "bf16"):
        assert sum(p[1:] == (dt, links) for p in params) == 4
        assert sum(p[1:] == (dt, blocks) for p in params) == 2
    consts = _hlo_values(hlo, "constant")
    big = [c for c in consts if c[0] > 2 ** 20]
    assert consts and not big, f"fields baked into the executable: {big}"


@pytest.mark.parametrize("n_src", [1, 8])
def test_verified_exit_program_compiles_for_v5e(one_chip, n_src):
    """The Wilson pair routes' verified exit (solvers/program.py) at
    24^4, one source and the eight of a batched call: compiles for the
    described chip with the resident links as parameters (nothing the
    size of a field is a constant), one kernel call per parity (the
    hop the reconstruction and M x share is not run twice), and what it
    holds besides its arguments and results stays far under the
    canonical M's tile-padded temporaries it replaced (6.9 GiB peak for
    one source, 13 GiB for eight: PERF_LEDGER, PR 28)."""
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.wilson import DiracWilsonPCPackedSloppy
    from quda_tpu.solvers import program as sprog
    geom = LatticeGeometry(DIMS)
    lead = () if n_src == 1 else (n_src,)

    def lower():
        lk = jax.ShapeDtypeStruct((4, 3, 3, L, L, YXH), jnp.complex64)
        op = jax.eval_shape(
            lambda e, o: DiracWilsonPCPackedSloppy.from_packed(
                geom, (e, o), 0.124, 0, F32, use_pallas=True,
                pallas_interpret=False), lk, lk)
        op = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one_chip,
                weak_type=s.weak_type), op)
        b = jax.ShapeDtypeStruct(lead + DIMS + (4, 3), jnp.complex64,
                                 sharding=one_chip)
        x = jax.ShapeDtypeStruct(*_psi(F32, lead), sharding=one_chip)
        return sprog._verified_exit_program.lower(op, b, x)
    compiled = _aot(lower)
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 2
    params = _hlo_values(hlo, "parameter")
    links = ",".join(str(d) for d in _links(F32)[0])
    assert sum(p[1:] == ("f32", links) for p in params) == 4
    big = [c for c in _hlo_values(hlo, "constant") if c[0] > 2 ** 20]
    assert not big, f"fields baked into the executable: {big}"
    assert compiled.memory_analysis().temp_size_in_bytes \
        < n_src * 0.4 * 2 ** 30


def test_batched_solve_program_compiles_for_v5e_combining_in_the_kernel(
        one_chip):
    """The eight-source solve program (solvers/program.py over
    ``block.batched_cg_pairs_loop``) at 24^4: an iteration is four
    MRHS kernels, the second with the combine epilogue (seven
    operands: three spinor blocks, ``xc``, the coefficient, the links;
    two results, the second the per-source sums of squares that are
    the loop's ``pAp``) and the fourth with its residual form (nine:
    ``rc`` and the per-source ``alpha`` besides; its first result the
    new ``r`` in the old one's buffer, its second the new ``|r|^2``),
    so what XLA is left with is the update of ``x`` and ``p``, one
    fusion; kappa and the links are parameters."""
    import re
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.wilson import DiracWilsonPCPackedSloppy
    from quda_tpu.solvers import program as sprog
    from quda_tpu.solvers.fused_iter import _resolve_check_every
    geom = LatticeGeometry(DIMS)

    def lower():
        lk = jax.ShapeDtypeStruct((4, 3, 3, L, L, YXH), jnp.complex64)
        op = jax.eval_shape(
            lambda e, o: DiracWilsonPCPackedSloppy.from_packed(
                geom, (e, o), 0.124, 0, F32, use_pallas=True,
                pallas_interpret=False), lk, lk)
        op = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one_chip,
                weak_type=s.weak_type), op)
        b = jax.ShapeDtypeStruct(*_psi(F32, (8,)), sharding=one_chip)
        key = (_resolve_check_every(None),
               sprog._LoopKnobs(False, None, None, None), False)
        return sprog._batched_cg_pairs_program.lower(op, b, 1e-6, 10000,
                                                     key=key)
    hlo = _aot(lower).as_text()
    calls = re.findall(r"%dslash_eo_pallas_packed_mrhs(_combine|_residual)?"
                       r"[.\d]* = (\(?)f32\[[^\n]*custom-call\(([^\n]*?)\), "
                       r"custom_call_target=\"tpu_custom_call\"", hlo)
    # a fused hop has a second, small result, the sums of squares of
    # what it stores: the first M's are the loop's pAp = |g5 M p|^2,
    # the last hop's the new |r|^2; each form runs under a kernel name
    # of its own, which the benchmark's patterns tell apart
    assert sorted((n, t, c.count("%")) for n, t, c in calls) == [
        ("", "", 5), ("", "", 5), ("_combine", "(", 7),
        ("_residual", "(", 9)], calls
    # the r update and its |r|^2 are the kernel's: the one XLA fusion
    # over the batch left in the loop is the update of x and p
    body = hlo[hlo.index("dslash_eo_pallas_packed_mrhs_residual"):]
    body = body[:body.index("ROOT")]
    batch = "f32[" + ",".join(str(d) for d in _psi(F32, (8,))[0]) + "]"
    fused = re.findall(r"(%[\w.]+) = \(?" + re.escape(batch)
                       + r"[^\n]* fusion\(", body)
    assert len(fused) == 1, fused
    links = ",".join(str(d) for d in _links(F32)[0])
    assert sum(p[1:] == ("f32", links)
               for p in _hlo_values(hlo, "parameter")) == 4
    big = [c for c in _hlo_values(hlo, "constant") if c[0] > 2 ** 20]
    assert not big, f"fields baked into the executable: {big}"


def test_ks_links_construction_compiles_for_v5e_lattice_minor(one_chip):
    """The resident KS term's links (ops/staggered_packed
    .ks_links_eo_pairs: phases and boundary folded, even-odd split,
    pairs) as ONE program at 24^4 from the canonical long links: what it
    holds beside its argument and result stays under 1 GiB (a
    canonical (...,3,3) temporary tile-pads ~57x: 5.4 GB each) and no
    field is baked into the executable.  The result's 95.6 MB are
    127.4 MB on the device: a 288-lane plane is held in 384 lanes."""
    from quda_tpu.ops import staggered_packed as spk

    def lower():
        g = jax.ShapeDtypeStruct((4,) + DIMS + (3, 3), jnp.complex64,
                                 sharding=one_chip)
        return spk.ks_links_eo_pairs.lower(g, DIMS, True, 3)
    compiled = _aot(lower)
    ma = compiled.memory_analysis()
    logical = 2 * 4 * 18 * (L ** 4 // 2) * 4
    assert logical <= ma.output_size_in_bytes <= (
        logical * 384 // 288 + 4096), (ma.output_size_in_bytes, logical)
    assert ma.temp_size_in_bytes < 2 ** 30, ma
    big = [c for c in _hlo_values(compiled.as_text(), "constant")
           if c[0] > 2 ** 20]
    assert not big, f"fields baked into the executable: {big}"


def test_hisq_solve_program_compiles_for_v5e_with_links_as_parameters(
        one_chip):
    """The improved-staggered single-source solve program
    (solvers/program.py on DiracStaggeredPCPairs, ``hermitian``: the
    operator applied once an iteration) at 24^4: compiles for the
    described chip with the fat and long links of both operators as
    parameters, and the served kernel form (served_forms: the
    two-pass scatter form v3) is there for both, under the name the
    benchmark's metrics read."""
    import re
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.staggered import DiracStaggeredPCPairs
    from quda_tpu.solvers import mixed
    from quda_tpu.solvers import program as sprog
    geom = LatticeGeometry(DIMS)
    lshape = (4, 3, 3, 2, L, L, YXH)

    def operators(fe, fo, le, lo):
        return tuple(DiracStaggeredPCPairs.from_packed(
            geom, (fe.astype(dt), fo.astype(dt)),
            (le.astype(dt), lo.astype(dt)), 0.04, 0, dt, use_pallas=True,
            pallas_interpret=False) for dt in (F32, BF16))

    def lower():
        lk = jax.ShapeDtypeStruct(lshape, F32)
        ops = jax.eval_shape(operators, lk, lk, lk, lk)
        hi, lo = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one_chip,
                weak_type=s.weak_type), ops)
        assert hi.hermitian and sprog.presents(hi, lo)
        assert hi._pallas_form == lo._pallas_form == "v3"
        b = jax.ShapeDtypeStruct((3, 2, L, L, YXH), F32,
                                 sharding=one_chip)
        key = (0.1, mixed.pair_inplace_config(BF16),
               sprog._LoopKnobs(False, None, None, None), True)
        return sprog._cg_reliable_program.lower(hi, lo, b, 1e-6, 10000,
                                                key=key)
    hlo = _aot(lower).as_text()
    # one M = two hops = four passes; the precise and the sloppy operator
    # each apply it (a pass returns f32 whatever it reads; the bf16
    # links among the parameters below say what the sloppy calls read)
    calls = re.findall(r"%dslash_staggered_eo_pallas_v3[.\d]* = f32"
                       r"\[[^\n]*tpu_custom_call", hlo)
    assert len(calls) >= 8, calls
    params = _hlo_values(hlo, "parameter")
    links = ",".join(str(d) for d in lshape)
    for dt in ("f32", "bf16"):      # fat and long, two parities (the
        # nested computations that slice their z rows list them again)
        assert sum(p[1:] == (dt, links) for p in params) >= 4
    big = [c for c in _hlo_values(hlo, "constant") if c[0] > 2 ** 20]
    assert not big, f"fields baked into the executable: {big}"


@pytest.mark.parametrize("program", ["prepare", "solve", "verified-exit"])
def test_hisq_batched_programs_compile_for_v5e(one_chip, program):
    """The three programs of a batched improved-staggered call
    (solvers/program.py on the resident f32 DiracStaggeredPCPairs, 8
    sources at 24^4) compile for the described chip on abstract
    operands: the links are parameters, the served MRHS form
    (models/staggered.served_forms) is the kernel in them, and the Hermitian
    solve applies it four times an iteration (one M = two hops = four
    passes), not eight."""
    import re
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.staggered import DiracStaggeredPCPairs
    from quda_tpu.solvers import program as sprog
    from quda_tpu.solvers.fused_iter import _resolve_check_every
    geom = LatticeGeometry(DIMS)
    lshape = (4, 3, 3, 2, L, L, YXH)
    n = 8

    def operator(fe, fo, le, lo):
        return DiracStaggeredPCPairs.from_packed(
            geom, (fe, fo), (le, lo), 0.04, 0, F32, use_pallas=True,
            pallas_interpret=False)

    def lower():
        lk = jax.ShapeDtypeStruct(lshape, F32)
        op = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one_chip,
                weak_type=s.weak_type),
            jax.eval_shape(operator, lk, lk, lk, lk))
        assert op.hermitian and sprog.presents(op)
        assert op._mrhs_form == "scatter_two_pass"
        x = jax.ShapeDtypeStruct((n, 3, 2, L, L, YXH), F32,
                                 sharding=one_chip)
        b = jax.ShapeDtypeStruct((n,) + DIMS + (1, 3), jnp.complex64,
                                 sharding=one_chip)
        if program == "prepare":
            return sprog._prepare_program.lower(op, b)
        if program == "verified-exit":
            return sprog._verified_exit_program.lower(op, b, x)
        key = (_resolve_check_every(None),
               sprog._LoopKnobs(False, None, None, None), True)
        return sprog._batched_cg_pairs_program.lower(op, x, 1e-6, 10000,
                                                     key=key)
    compiled = _aot(lower)
    hlo = compiled.as_text()
    calls = re.findall(r"%dslash_staggered_eo_pallas_v3_mrhs[.\d]* = f32"
                       r"\[[^\n]*tpu_custom_call", hlo)
    assert len(calls) == {"prepare": 2, "solve": 4,
                          "verified-exit": 4}[program], calls
    params = _hlo_values(hlo, "parameter")
    links = ",".join(str(d) for d in lshape)
    assert sum(p[1:] == ("f32", links) for p in params) >= 4
    big = [c for c in _hlo_values(hlo, "constant") if c[0] > 2 ** 20]
    assert not big, f"fields baked into the executable: {big}"
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("program", ["prepare", "solve", "verified-exit"])
def test_clover_batched_programs_compile_for_v5e(one_chip, program):
    """The three programs of a batched clover call (solvers/program.py
    on the resident f32 DiracCloverPCPairs ``with_full_diag``, 8 sources
    at 24^4, the served batch form) compile for the described chip on
    abstract operands: links, the blocks of both parities and A_q are
    parameters; the solve applies the two fused MRHS kernels twice an
    iteration, on the full-Z route since PR 47 (three spinor operands,
    ``xc`` and the coefficient for ``diag_hop``, links, blocks LAST: six
    and eight operands where the z-blocked calls had eight and ten; the
    kernels alone, with their ``vmem_limit_bytes`` and the z-blocked
    fallback, are cases of ``test_kernel_compiles_for_v5e``), and since
    PR 48 it takes its step from the operator: the first M's K2 call
    has a second result, the sums of squares that are ``pAp``, the
    second's is the residual form (ten operands: ``rc`` and ``alpha``
    besides; the new ``r`` and ``|r|^2``), and the loop's body is those
    four custom calls and ONE fusion over a batch-sized operand, the
    update of ``x`` and ``p``; the entry
    folds Mdag in (one more of each fused kernel behind the bare hop of
    prepare);
    the exit is two bare MRHS hops and XLA's block products, and what it
    holds with its arguments and results leaves the rest of the chip to
    the resident term and the caller's batch (ISSUE 46's rule on the
    peak: 15.0 of 15.75 GiB)."""
    import re
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.clover import DiracCloverPCPairs
    from quda_tpu.solvers import program as sprog
    from quda_tpu.solvers.fused_iter import _resolve_check_every
    geom = LatticeGeometry(DIMS)
    half = (L, L, YXH)
    n = 8

    def operator(links_e, links_o, a_p, ainv_q, a_q):
        return DiracCloverPCPairs.from_packed(
            geom, (links_e, links_o), 0.124, 0, a_p, ainv_q, F32,
            use_pallas=True, pallas_interpret=False,
            form="pallas").with_full_diag(a_q)

    def lower():
        lk = jax.ShapeDtypeStruct((4, 3, 3) + half, jnp.complex64)
        bk = jax.ShapeDtypeStruct((2, 6, 6) + half, jnp.complex64)
        aq = jax.ShapeDtypeStruct((2, 6, 6, 2) + half, F32)
        op = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one_chip,
                weak_type=s.weak_type),
            jax.eval_shape(operator, lk, lk, bk, bk, aq))
        assert sprog.presents(op) and op._mrhs_form() == "pallas"
        x = jax.ShapeDtypeStruct(*_psi(F32, (n,)), sharding=one_chip)
        b = jax.ShapeDtypeStruct((n,) + DIMS + (4, 3), jnp.complex64,
                                 sharding=one_chip)
        if program == "prepare":
            return sprog._prepare_program.lower(op, b)
        if program == "verified-exit":
            return sprog._verified_exit_program.lower(op, b, x)
        key = (_resolve_check_every(None),
               sprog._LoopKnobs(False, None, None, None), False)
        return sprog._batched_cg_pairs_program.lower(op, x, 1e-6, 10000,
                                                     key=key)
    compiled = _aot(lower)
    hlo = compiled.as_text()
    calls = sorted(re.findall(r"%(dslash_eo_pallas\w*?)[.\d]* = \(?f32"
                              r"\[[^\n]*tpu_custom_call", hlo))
    fused = ["dslash_eo_pallas_diag_hop_mrhs", "dslash_eo_pallas_post_mrhs"]
    bare = "dslash_eo_pallas_packed_mrhs"
    assert calls == {"prepare": sorted(fused + [bare]),
                     "solve": sorted(2 * fused),
                     "verified-exit": [bare, bare]}[program], calls
    # the fused kernels took the full-Z route: three psi operands, not five
    operands = sorted((n, t, c.count("%")) for n, t, c in re.findall(
        r"%(dslash_eo_pallas_(?:post|diag_hop)_mrhs)[.\d]* = (\(?)f32\["
        r"[^\n]*custom-call\(([^\n]*?)\), "
        r"custom_call_target=\"tpu_custom_call\"", hlo))
    post, k2 = "dslash_eo_pallas_post_mrhs", "dslash_eo_pallas_diag_hop_mrhs"
    assert operands == {
        "verified-exit": [], "prepare": [(k2, "", 8), (post, "", 6)],
        "solve": [(k2, "(", 8), (k2, "(", 10), (post, "", 6),
                  (post, "", 6)]}[program], operands
    if program == "solve":
        # XLA makes no gamma5, dot, r update or |r|^2: what is left of
        # the iteration over the batch is the update of x and p
        loop = _hlo_computation(hlo, re.search(
            r" while\([^\n]*body=%([\w.\-]+)", hlo).group(1))
        assert len(re.findall(r"tpu_custom_call", loop)) == 4
        batch = "f32[" + ",".join(str(d) for d in _psi(F32, (n,))[0]) + "]"
        fused = [ln.split(" = ")[0].strip() for ln in loop.split("\n")
                 if " fusion(" in ln and batch in ln]
        assert len(fused) == 1, fused
        assert not re.findall(re.escape(batch) + r"\S* copy\(", loop)
    params = _hlo_values(hlo, "parameter")
    links = ",".join(str(d) for d in _links(F32)[0])
    blocks = ",".join(str(d) for d in (2, 6, 6, 2, L, L, YXH))
    assert sum(p[1:] == ("f32", links) for p in params) >= 2
    assert sum(p[1:] == ("f32", blocks) for p in params) == {
        "prepare": 2, "solve": 2, "verified-exit": 3}[program]
    big = [c for c in _hlo_values(hlo, "constant") if c[0] > 2 ** 20]
    assert not big, f"fields baked into the executable: {big}"
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < n * 0.4 * 2 ** 30, ma
    # beside 4.2 GiB of resident gauge and term (clover24_single's peak)
    # and the caller's 1.3 GiB batch of sources
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert need < 7 * 2 ** 30, ma


@pytest.mark.parametrize("program", ["solve", "verified-exit"])
def test_hisq_multishift_programs_compile_for_v5e(one_chip, program):
    """The two programs of a multi-shift improved-staggered call that
    no other route builds (solvers/program.py on the resident f32
    DiracStaggeredPCPairs, fourteen shifts at 24^4; its prepare is the
    single-source one above) compile for the described chip on abstract
    operands: links AND shifts are parameters (other offsets of the
    same count are the same executable), the loop applies the
    single-source served form four passes an iteration and updates its
    live shifts in place, and the exit applies the MRHS form to the
    fourteen solutions as one batch."""
    import re
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.staggered import DiracStaggeredPCPairs
    from quda_tpu.solvers import program as sprog
    geom = LatticeGeometry(DIMS)
    lshape = (4, 3, 3, 2, L, L, YXH)
    n = 14

    def operator(fe, fo, le, lo):
        return DiracStaggeredPCPairs.from_packed(
            geom, (fe, fo), (le, lo), 0.04, 0, F32, use_pallas=True,
            pallas_interpret=False)

    def lower():
        lk = jax.ShapeDtypeStruct(lshape, F32)
        op = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one_chip,
                weak_type=s.weak_type),
            jax.eval_shape(operator, lk, lk, lk, lk))
        assert op.hermitian and sprog.presents(op)
        b = jax.ShapeDtypeStruct((3, 2, L, L, YXH), F32,
                                 sharding=one_chip)
        per_shift = lambda dt: jax.ShapeDtypeStruct((n,), dt,
                                                    sharding=one_chip)
        if program == "solve":
            # the update's form as multishift.update_form reads it on
            # a TPU backend (here the backend is the CPU): the kernel
            key = (sprog._LoopKnobs(False, None, None, None), True,
                   "pallas")
            return sprog._multishift_program.lower(
                op, b, per_shift(F32), 1e-6, 10000, key=key)
        x = jax.ShapeDtypeStruct((n, 3, 2, L, L, YXH), F32,
                                 sharding=one_chip)
        return sprog._verified_exit_shifts_program.lower(
            op, b, x, per_shift(F32), per_shift(jnp.bool_),
            per_shift(F32), 1e-4)
    compiled = _aot(lower)
    hlo = compiled.as_text()
    name = {"solve": "dslash_staggered_eo_pallas_v3",
            "verified-exit": "dslash_staggered_eo_pallas_v3_mrhs"}[program]
    calls = re.findall(rf"%{name}[.\d]* = f32\[[^\n]*tpu_custom_call", hlo)
    assert len(calls) == 4, calls
    if program == "solve":
        # the live shifts' update is ONE kernel on the carried stacks
        # (operands and results bitcasts of them): the operator is
        # traced once whatever the number of live shifts, and nothing
        # the size of a stack is copied (a conditional around an XLA
        # update copies one out of and into on-chip memory a branch)
        assert len(re.findall(r"%multishift_update_pallas[.\d]* = \("
                              r"[^\n]*tpu_custom_call", hlo)) == 1
        stack = L * L * YXH * 3 * 2 * n * 4
        assert not [c for c in _hlo_values(hlo, "copy") if c[0] >= stack]
        assert "conditional(" not in hlo
    params = _hlo_values(hlo, "parameter")
    links = ",".join(str(d) for d in lshape)
    assert sum(p[1:] == ("f32", links) for p in params) >= 4
    assert sum(p[1:] == ("f32", str(n)) for p in params) >= 1
    big = [c for c in _hlo_values(hlo, "constant") if c[0] > 2 ** 20]
    assert not big, f"fields baked into the executable: {big}"
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 30


@pytest.mark.parametrize("program", ["solve", "verified-exit"])
def test_clover_multishift_programs_compile_for_v5e(one_chip, program):
    """The two programs of a multi-shift Wilson-clover call that no
    other route builds (solvers/program.py on the resident f32
    DiracCloverPCPairs, fourteen shifts at 24^4; its prepare is the
    single-source case of ``prepare_normal_pairs``) compile for the
    described chip on abstract operands: links, blocks AND shifts are
    parameters (other offsets, kappa or csw are the same executable);
    the loop takes the normal-equations side of ``_multishift_program``
    (``hermitian`` False in its key): the two fused single-source f32
    kernels twice an iteration, and updates its live shifts in place on
    two 223 MB stacks (neither fits the chip's 128 MiB of on-chip
    memory); the exit applies the fused MRHS kernels to the fourteen
    solutions as one batch, which ``mrhs_route`` serves on the full-Z
    route at N = 14 as at 8 (three spinor operands).  The solve
    program's HBM need is asserted under 8 GiB as an upper bound: such
    bounds over-state (PERF.md section 7 (40))."""
    import re
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.clover import DiracCloverPCPairs
    from quda_tpu.solvers import program as sprog
    geom = LatticeGeometry(DIMS)
    half = (L, L, YXH)
    n = 14

    def operator(links_e, links_o, a_p, ainv_q):
        return DiracCloverPCPairs.from_packed(
            geom, (links_e, links_o), 0.32, 0, a_p, ainv_q, F32,
            use_pallas=True, pallas_interpret=False, form="pallas")

    def lower():
        lk = jax.ShapeDtypeStruct((4, 3, 3) + half, jnp.complex64)
        bk = jax.ShapeDtypeStruct((2, 6, 6) + half, jnp.complex64)
        op = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=one_chip,
                weak_type=s.weak_type),
            jax.eval_shape(operator, lk, lk, bk, bk))
        assert sprog.presents(op) and not getattr(op, "hermitian", False)
        assert op._mrhs_form() == "pallas"
        b = jax.ShapeDtypeStruct(*_psi(F32), sharding=one_chip)
        per_shift = lambda dt: jax.ShapeDtypeStruct((n,), dt,
                                                    sharding=one_chip)
        if program == "solve":
            # the update's form as multishift.update_form reads it on
            # a TPU backend (here the backend is the CPU): the kernel
            key = (sprog._LoopKnobs(False, None, None, None), False,
                   "pallas")
            return sprog._multishift_program.lower(
                op, b, per_shift(F32), 1e-6, 10000, key=key)
        x = jax.ShapeDtypeStruct(*_psi(F32, (n,)), sharding=one_chip)
        return sprog._verified_exit_shifts_program.lower(
            op, b, x, per_shift(F32), per_shift(jnp.bool_),
            per_shift(F32), 1e-4)
    compiled = _aot(lower)
    hlo = compiled.as_text()
    calls = sorted(re.findall(r"%(dslash_eo_pallas\w*?)[.\d]* = \(?f32"
                              r"\[[^\n]*tpu_custom_call", hlo))
    single = ["dslash_eo_pallas_diag_hop", "dslash_eo_pallas_post"]
    assert calls == sorted(2 * ([s + "_mrhs" for s in single]
                                if program == "verified-exit"
                                else single)), calls
    if program == "solve":
        # the live shifts' update is ONE kernel on the carried stacks
        # and nothing the size of a stack is copied
        assert len(re.findall(r"%multishift_update_pallas[.\d]* = \("
                              r"[^\n]*tpu_custom_call", hlo)) == 1
        stack = L * L * YXH * 24 * n * 4
        assert not [c for c in _hlo_values(hlo, "copy") if c[0] >= stack]
        assert "conditional(" not in hlo
    else:
        # the fused MRHS kernels took the full-Z route at N = 14:
        # three psi operands (six and eight operands in all), not five
        operands = sorted(c.count("%") for c in re.findall(
            r"%dslash_eo_pallas_(?:post|diag_hop)_mrhs[.\d]* = \(?f32\["
            r"[^\n]*custom-call\(([^\n]*?)\), "
            r"custom_call_target=\"tpu_custom_call\"", hlo))
        assert operands == [6, 6, 8, 8], operands
    params = _hlo_values(hlo, "parameter")
    links = ",".join(str(d) for d in _links(F32)[0])
    blocks = ",".join(str(d) for d in (2, 6, 6, 2, L, L, YXH))
    assert sum(p[1:] == ("f32", links) for p in params) >= 2
    assert sum(p[1:] == ("f32", blocks) for p in params) == 2
    assert sum(p[1:] == ("f32", str(n)) for p in params) >= 1
    big = [c for c in _hlo_values(hlo, "constant") if c[0] > 2 ** 20]
    assert not big, f"fields baked into the executable: {big}"
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    print(f"clover multishift {program}: arguments "
          f"{ma.argument_size_in_bytes / 2**30:.2f} GiB, outputs "
          f"{ma.output_size_in_bytes / 2**30:.2f}, temporaries "
          f"{ma.temp_size_in_bytes / 2**30:.2f}")
    assert need < 8 * 2 ** 30, ma


MOBIUS_LS = 12


@pytest.mark.parametrize("program", ["solve", "verified-exit"])
def test_mobius_programs_compile_for_v5e_under_14_gib(one_chip, program):
    """The solve and exit programs of the Möbius resident route
    (solvers/program.py on DiracMobiusPCPairs, the operators the shapes
    interfaces/quda_api._mobius_term_program returns) at 24^4 x Ls 12,
    the cell's size: each compiles
    for the described chip with the Ls-batched kernel serving the hop
    (``MEASURED_LS_HOP_FORM``: the MRHS Wilson kernel, twelve planes on
    its source axis, f32 and bf16), the links and the (Ls, Ls) blocks
    parameters (nothing the size of a field a constant), and arguments,
    results and temporaries together under 14 GiB of the chip's 15.75:
    the canonical 5-d source and solution of the exit are 1.9 GiB each
    as the chip tiles them (a 24-wide minor axis), the rule on the peak
    of ISSUE 42 (the term and entry programs compile in a scratch run
    to 1.8 and 6.2 GiB: PERF.md, PR 42).  Since PR 44 the (Ls, Ls)
    blocks are kernels on the hop's layout: the module holds no dot,
    the loop body a sloppy MdagM's six s-block calls between its four
    hops and no copy of a 5-d vector."""
    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.interfaces import quda_api as api
    from quda_tpu.models import domain_wall as mdw
    from quda_tpu.solvers import mixed
    from quda_tpu.solvers import program as sprog
    geom = LatticeGeometry(DIMS)
    blocks = mdw.m5_block_pairs(MOBIUS_LS, 1.8, 0.03, 1.5, 0.5)
    static = (0, True, MOBIUS_LS, True, False)
    stores = (jnp.dtype(F32), jnp.dtype(BF16))
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip,
                                       weak_type=s.weak_type), tree)

    def lower():
        g = jax.ShapeDtypeStruct((4,) + DIMS + (3, 3), jnp.complex64,
                                 sharding=one_chip)
        ops = on_chip(jax.eval_shape(
            lambda g: api._mobius_term_program(g, blocks, geom, static,
                                               stores), g))
        hi, lo = ops[stores[0]], ops[stores[1]]
        assert hi._op_form == lo._op_form == mdw.MEASURED_LS_HOP_FORM
        b = jax.ShapeDtypeStruct((MOBIUS_LS,) + DIMS + (4, 3),
                                 jnp.complex64, sharding=one_chip)
        x = jax.ShapeDtypeStruct(*_psi(F32, (MOBIUS_LS,)),
                                 sharding=one_chip)
        if program == "verified-exit":
            return sprog._verified_exit_program.lower(hi, b, x)
        key = (0.1, mixed.pair_inplace_config(BF16),
               sprog._LoopKnobs(False, None, None, None), False)
        return sprog._cg_reliable_program.lower(hi, lo, x, 1e-6, 10000,
                                                key=key)
    compiled = _aot(lower)
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert peak < 14 * 2 ** 30, ma
    hlo = compiled.as_text()
    big = [c for c in _hlo_values(hlo, "constant") if c[0] > 2 ** 20]
    assert not big, f"fields baked into the executable: {big}"
    import re
    hops = re.findall(r"%dslash_eo_pallas_packed_mrhs[.\d]* = (\w+)\["
                      r"[^\n]*tpu_custom_call", hlo)
    # exit: the hop the reconstruction and M x share, and the other
    # parity's; the solve: four a sloppy MdagM, four a precise one
    if program == "verified-exit":
        assert hops == ["f32"] * 2, hops
    else:
        assert hops.count("bf16") >= 2 and "f32" in hops, hops
    links = ",".join(str(d) for d in _links(F32)[0])
    params = _hlo_values(hlo, "parameter")
    assert sum(p[1:] == ("f32", links) for p in params) == 4
    # the (Ls, Ls) blocks are the VPU kernel on the hop's layout, not a
    # dot XLA lays the vectors out for (PR 44): no dot in the module ...
    assert not re.findall(r" (?:dot|convolution)\(", hlo)
    sblock = lambda text: sorted(re.findall(
        r"%(mobius_sblock(?:_axpy)?_pallas)[.\d]* = (\w+)\["
        r"[^\n]*tpu_custom_call", text))
    if program == "verified-exit":
        # M5' x_p, M5^-1 (...), M5 x_p, M5' x_q, M5 x_q
        assert sblock(hlo) == [("mobius_sblock_pallas", "f32")] * 5
    else:
        # ... and the loop body holds a sloppy MdagM's six products (the
        # last of each M with x - 1/4 ... in it) between its four hops,
        # with no copy of a 5-d vector around either
        loop = _hlo_computation(hlo, re.search(
            r" while\([^\n]*body=%([\w.\-]+)", hlo).group(1))
        assert sblock(loop) == (
            [("mobius_sblock_axpy_pallas", "bf16")] * 2
            + [("mobius_sblock_pallas", "bf16")] * 4)
        assert len(re.findall(r"%dslash_eo_pallas_packed_mrhs[.\d]* = ",
                              loop)) == 4
        vec = rf"\[{MOBIUS_LS},\d,3,2,{L},{L},{YXH}\]"
        assert not re.findall(vec + r"\S* copy\(", loop)
