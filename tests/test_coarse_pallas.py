"""Fused pallas coarse-stencil kernel (ops/coarse_pallas.py).

Reference behavior: lib/dslash_coarse.cu — one kernel applies X plus
all 8 directional Y links.  The TPU kernel is pinned against the XLA
reference contraction (coarse_apply_ref) in interpreter mode, the
PairCoarseOperator routing (use_pallas) against the einsum and
embedding apply forms, the VMEM block picker, the
QUDA_TPU_MG_COARSE_FORM resolution, and the nc-parametric traffic
model against its canonical KERNEL_MODELS row (the drift-lint anchor).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from quda_tpu.mg.coarse import DIRS
from quda_tpu.mg.pair import PairCoarseOperator, resolve_coarse_form
from quda_tpu.obs.roofline import KERNEL_MODELS
from quda_tpu.ops.coarse_pallas import (_pick_bs, coarse_apply_pallas,
                                        coarse_apply_ref, coarse_model)
from quda_tpu.utils import config as qconf

LATC = (2, 2, 2, 2)
NVEC = 4


@pytest.fixture(autouse=True)
def _fresh_knobs():
    qconf.reset_cache()
    yield
    qconf.reset_cache()


def _op(seed=0, n_vec=NVEC, latc=LATC):
    nc = 2 * n_vec
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    shape = latc + (nc, nc, 2)
    x = jax.random.normal(ks[0], shape, jnp.float32)
    y = {d: jax.random.normal(k, shape, jnp.float32)
         for d, k in zip(DIRS, ks[1:])}
    return PairCoarseOperator(x, y, n_vec)


def _probe(seed, n_vec=NVEC, latc=LATC):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             latc + (2, n_vec, 2), jnp.float32)


def test_kernel_matches_ref_on_stacked_operands():
    """Same stacked operands, same contraction, same accumulation
    dtype: the kernel output equals the XLA reference to f32
    roundoff."""
    S, E = 16, 16
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    links = jax.random.normal(k1, (9, S, E, E), jnp.float32)
    psi9 = jax.random.normal(k2, (9, S, E), jnp.float32)
    out = coarse_apply_pallas(links, psi9, interpret=True)
    ref = coarse_apply_ref(links, psi9)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5 * scale


def test_pallas_apply_matches_einsum_and_embedding():
    """PairCoarseOperator.M with use_pallas reproduces the einsum form
    (and the embedding form agrees too) on the same operator."""
    op = _op()
    v = _probe(5)
    ref = op.M(v)                                      # einsum form
    emb = dataclasses.replace(op, use_embedding=True).M(v)
    pal = dataclasses.replace(op, use_pallas=True,
                              pallas_interpret=True).M(v)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(emb - ref))) < 2e-5 * scale
    assert float(jnp.max(jnp.abs(pal - ref))) < 2e-5 * scale


@pytest.mark.slow
def test_pallas_apply_matches_at_production_nc():
    """Heavy case: 4^4 coarse lattice at n_vec=8 (E=32) — interpreter
    mode, so marked slow."""
    op = _op(seed=11, n_vec=8, latc=(4, 4, 4, 4))
    v = _probe(12, n_vec=8, latc=(4, 4, 4, 4))
    ref = op.M(v)
    pal = dataclasses.replace(op, use_pallas=True,
                              pallas_interpret=True).M(v)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(pal - ref))) < 2e-5 * scale


def test_explicit_block_sites_must_divide():
    S, E = 16, 16
    links = jnp.zeros((9, S, E, E), jnp.float32)
    psi9 = jnp.zeros((9, S, E), jnp.float32)
    with pytest.raises(ValueError, match="does not divide"):
        coarse_apply_pallas(links, psi9, interpret=True, block_sites=3)
    out = coarse_apply_pallas(links, psi9, interpret=True, block_sites=4)
    assert out.shape == (S, E)


def test_pick_bs_divides_and_respects_budget():
    S, E = 16, 16
    bs = _pick_bs(S, E)
    assert S % bs == 0
    # a starved budget forces the minimum ADMISSIBLE block (the site
    # axis is sublane-tiled on the chip: a multiple of 8, or S itself);
    # a generous one takes the whole lattice in one grid step; a budget
    # below the minimum block is an error, not a 1-site block the
    # compiler would refuse
    with qconf.overrides(QUDA_TPU_PALLAS_VMEM_MB="0.7"):
        assert _pick_bs(S, E) == 8
    with qconf.overrides(QUDA_TPU_PALLAS_VMEM_MB="512"):
        assert _pick_bs(S, E) == S
    with qconf.overrides(QUDA_TPU_PALLAS_VMEM_MB="0.08"):
        with pytest.raises(ValueError, match="multiple of 8"):
            _pick_bs(S, E)
    # the production MG shape (24^4 fine, 4^4 blocks, 24 vectors): the
    # largest fitting DIVISOR is 27, which the chip's compiler refuses
    assert _pick_bs(1296, 48) == 24


def test_resolve_coarse_form_pins():
    """Explicit QUDA_TPU_MG_COARSE_FORM pins are honored; 'auto'
    off-chip falls back to the static QUDA_TPU_MG_EMBED default
    (interpret timings would be meaningless to race)."""
    op = _op(seed=21)
    with qconf.overrides(QUDA_TPU_MG_COARSE_FORM="pallas"):
        r = resolve_coarse_form(op)
        assert r.use_pallas and r.pallas_interpret   # off-chip
    with qconf.overrides(QUDA_TPU_MG_COARSE_FORM="embed"):
        r = resolve_coarse_form(op)
        assert r.use_embedding and not r.use_pallas
    with qconf.overrides(QUDA_TPU_MG_COARSE_FORM="einsum"):
        r = resolve_coarse_form(op)
        assert not r.use_embedding and not r.use_pallas
    with qconf.overrides(QUDA_TPU_MG_COARSE_FORM="auto",
                         QUDA_TPU_MG_EMBED="1"):
        r = resolve_coarse_form(op)
        assert r.use_embedding and not r.use_pallas
    with qconf.overrides(QUDA_TPU_MG_COARSE_FORM="auto",
                         QUDA_TPU_MG_EMBED="0"):
        r = resolve_coarse_form(op)
        assert not r.use_embedding and not r.use_pallas


def test_coarse_model_anchors_kernel_models_row():
    """The nc-parametric traffic model at the canonical probe size
    (n_vec=4 -> Nc=8, E=16) IS the KERNEL_MODELS row the drift lint
    checks — a drift between them would let bench attribution disagree
    with the linted model."""
    mdl = coarse_model(8)
    row = KERNEL_MODELS["mg_coarse_pallas"]
    assert mdl["flops_per_site"] == row["flops_per_site"] == 4608
    assert mdl["bytes_per_site"] == row["bytes_per_site"] == 9856
    # amortisation sanity: traffic grows ~E^2 with nc, flops exactly
    big = coarse_model(16)
    assert big["flops_per_site"] == 4 * mdl["flops_per_site"]
