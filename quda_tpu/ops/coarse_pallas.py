"""Fused coarse-stencil pallas kernel: the MG coarse M in one launch.

Reference behavior: QUDA's coarse dslash (lib/dslash_coarse.cu /
include/kernels/dslash_coarse.cuh) applies the nearest-neighbour coarse
operator as one kernel over sites — X (coarse clover) plus the 8
directional Y links — with the MMA path batching the per-site
(Nc x Nc) matvecs onto tensor cores.

TPU-native form: the coarse operator lives on the interleaved real
embedding (mg/pair.py: complex g -> [[re,-im],[im,re]], so a complex
(Nc x Nc) matvec is ONE real (E x E) matvec with E = 2*Nc).  The XLA
einsum apply issues 9 separate contractions with 8 intermediate
accumulation buffers materialised between them; this kernel streams a
block of coarse sites through VMEM ONCE, applying all 9 embedded link
matrices and accumulating in registers — the single-pass shape the
fused dslash kernels own for the fine levels.

Layout:

* links: (9, S, E, E) f32 — [diag, then DIRS order] embedded link
  stack over the flattened coarse lattice S = prod(latc);
* psi:   (9, S, E) f32 — the input's interleaved flat form and its 8
  pre-rolled neighbour copies (same DIRS order).  Pre-rolling outside
  the kernel costs 8 small field copies — at production Nc the link
  traffic dominates the model >90%, and it keeps the grid free of
  cross-block neighbour splicing (the coarse lattice is small; the
  rolls are XLA's).

Traffic model (per coarse site, f32): links 36*E^2 B + the 9 psi
stream reads 36*E B + out write 4*E B = 36*E^2 + 40*E — the
obs/roofline.py ``mg_coarse_pallas`` row is this arithmetic at the
canonical probe size (the cost-drift lint cross-checks it against the
XLA reference contraction and the operand footprint; obs/costmodel.py
family ``mg_coarse``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32

# the stacked reference contraction the kernel computes (and is
# bit-matched against in tests): out[s] = sum_k L[k, s] @ psi[k, s]
_SPEC = "ksab,ksb->sa"


def coarse_apply_ref(links: jnp.ndarray, psi9: jnp.ndarray) -> jnp.ndarray:
    """XLA reference of the fused apply on the same stacked operands —
    the bit-match witness and the cost-model flops reference."""
    return jnp.einsum(_SPEC, links, psi9, preferred_element_type=F32)


def _pick_bs(S: int, E: int) -> int:
    """Largest admissible site block whose VMEM working set (9 link
    blocks + 9 psi blocks + out, f32) fits the scoped budget
    (QUDA_TPU_PALLAS_VMEM_MB — shared with the fine-level kernels).
    Admissible = a divisor of S that is a multiple of 8 or S itself:
    the site axis is the second-to-last dimension of the psi/out
    blocks, which Mosaic tiles by 8 sublanes (a 27-site block at
    S=1296 is refused on the chip)."""
    from ..utils import config as qconf
    budget = int(float(qconf.get("QUDA_TPU_PALLAS_VMEM_MB",
                                 fresh=True)) * 2 ** 20)
    epad = -(-E // 128) * 128          # lane padding
    per_site = 4 * (9 * E * epad + 9 * epad + epad)
    fits = [bs for bs in range(1, S + 1)
            if S % bs == 0 and (bs % 8 == 0 or bs == S)
            and bs * per_site <= budget]
    if not fits:
        raise ValueError(
            f"coarse_apply_pallas: no site block of S={S} (a multiple "
            f"of 8, or S) fits the {budget >> 20} MiB VMEM budget at "
            f"E={E} (QUDA_TPU_PALLAS_VMEM_MB)")
    return fits[-1]


@functools.partial(jax.jit, static_argnames=("interpret", "block_sites"))
def coarse_apply_pallas(links: jnp.ndarray, psi9: jnp.ndarray,
                        interpret: bool = False,
                        block_sites: int | None = None) -> jnp.ndarray:
    """Fused coarse M: links (9, S, E, E), psi9 (9, S, E) -> (S, E).

    One grid step owns a block of coarse sites: all 9 link blocks and
    the 9 psi blocks are VMEM-resident, the 9 matvecs accumulate in one
    einsum (MXU-batched over the site block), the output is written
    once.  Bit-matches :func:`coarse_apply_ref` (same contraction, same
    accumulation dtype) — pinned in tests/test_coarse_pallas.py."""
    from jax.experimental import pallas as pl

    nine, S, E = psi9.shape
    assert nine == 9 and links.shape == (9, S, E, E), (links.shape,
                                                       psi9.shape)
    bs = block_sites if block_sites is not None else _pick_bs(S, E)
    if S % bs != 0:
        raise ValueError(f"block_sites={bs} does not divide S={S}")

    def kernel(l_ref, p_ref, o_ref):
        o_ref[...] = jnp.einsum(_SPEC, l_ref[...], p_ref[...],
                                preferred_element_type=F32)

    return pl.pallas_call(
        kernel,
        grid=(S // bs,),
        in_specs=[pl.BlockSpec((9, bs, E, E), lambda i: (0, i, 0, 0)),
                  pl.BlockSpec((9, bs, E), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((bs, E), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, E), F32),
        interpret=interpret,
    )(links, psi9)


def coarse_model(nc: int) -> dict:
    """Analytic per-coarse-site flops/bytes of the fused apply at a
    given coarse color count Nc (E = 2*Nc): the nc-parametric form of
    the canonical ``mg_coarse_pallas`` KERNEL_MODELS row — bench rows
    at non-canonical Nc attribute through this (obs/roofline.attribute
    accepts the explicit model)."""
    e = 2 * nc
    return {"flops_per_site": 18 * e * e,       # 9 real ExE matvecs
            # links once + 9 psi stream reads + out, f32
            "bytes_per_site": 36 * e * e + 40 * e}
