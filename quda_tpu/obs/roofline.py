"""Roofline attribution: PERF.md traffic models joined with wall-times.

Reference behavior: QPhiX/QUDA performance work reports every kernel as
achieved-vs-roofline (arXiv:1510.08879; QUDA's per-kernel GFLOPS+GB/s
profiler tsv, lib/tune.cpp:528-610).  PERF.md rounds 2-8 derived those
numbers BY HAND from ad-hoc bench prints; this module is the single
home for (a) the per-site flops/bytes models of every kernel form and
(b) the arithmetic joining them with measured seconds into
achieved-GFLOPS / achieved-BW / %-of-published-peak rows — the bench
harness and the API solves consume these helpers instead of private
math, so a model update lands everywhere at once.

Peaks are the PUBLISHED per-chip figures of the device that is present
(:data:`DEVICE_PEAKS`, keyed by ``device_kind``).  A TPU whose kind is
not in the table is an error, never a default; off-TPU (CPU CI) there
is no peak and the percent-of-peak columns are None.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

# Published per-chip peaks, keyed by jax's ``device_kind``.
# TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM (Google Cloud documentation,
# "TPU v5e").  The flop peak is the bf16 MXU rate — the f32 VPU stencil
# kernels cannot approach it; the bandwidth peak is the one that bounds
# them.
DEVICE_PEAKS: Dict[str, dict] = {
    "TPU v5 lite": {"gflops": 197000.0, "gbps": 819.0,
                    "source": "Google Cloud documentation, 'TPU v5e'"},
}


def device_peaks(device_kind: Optional[str] = None) -> Optional[dict]:
    """The published peaks of ``device_kind`` (default: the first jax
    device).  None off-TPU; KeyError for a TPU kind the table lacks."""
    if device_kind is None:
        import jax
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            return None
        device_kind = dev.device_kind
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"obs/roofline.DEVICE_PEAKS (known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[device_kind]


# Per-site flops / bytes models (f32 pairs, per UPDATED site, one
# operator application).  Sources: PERF.md round 2 (v2 traffic table),
# round 4 (reconstruct-12), PR 31 (MRHS 288 + 576/N), round 8
# (staggered fat+Naik 1512 B).  ``bytes_per_site``
# None = no credible traffic model for the form (no BW attribution).
KERNEL_MODELS: Dict[str, dict] = {
    # gather-form v2: psi 5x96 + out 96 + gauge 288 fwd + 288 bw copy
    "wilson_v2": {"flops_per_site": 1320, "bytes_per_site": 1152},
    # v2 with reconstruct-12 links: BOTH resident link arrays (forward
    # and the pre-shifted backward copy, built from the compressed
    # arrays) shrink 288 -> 192 B/site, so 1152 - 2*96
    "wilson_v2_r12": {"flops_per_site": 1320, "bytes_per_site": 960},
    # MRHS, the full-Z route with two time-slices a step
    # (ops/wilson_pallas_packed._mrhs_route: what one chip's 24^4 runs):
    # psi 2x96 + out 96 + gauge 576/N per RHS.  One slice a step reads
    # psi three times (384 + 576/N), the z-blocked fallback of larger
    # local volumes five (576 + 576/N); neither has a row of its own
    "wilson_mrhs": {"flops_per_site": 1320,
                    "bytes_per_site": lambda nrhs: 288.0 + 576.0 / nrhs},
    # precision storage forms (PERF.md round 16).  r12f = r12 storage
    # + copy-free scatter backward on the gather psi path: gauge reads
    # are g_here 192 + g_there xyz 144 + g_t plane 48 = 384 — exactly
    # the r12 forward+backward-copy 2x192, so traffic EQUALS wilson_v2
    # _r12; the win is residency (no 192 B/site backward array), not
    # bandwidth.
    "wilson_v2_r12f": {"flops_per_site": 1320, "bytes_per_site": 960},
    # fold: re/im interleaved into sublane rows — same logical bytes as
    # v2 at f32 (the fold changes tile SHAPE, not byte count)...
    "wilson_v2_fold": {"flops_per_site": 1320, "bytes_per_site": 1152},
    # ...but at bf16 storage the fold makes every (16,128) tile FULL
    # (no half-empty sublane pads), so the moved bytes finally match
    # the logical 2-byte element count: 1152/2
    "wilson_v2_bf16_fold": {"flops_per_site": 1320,
                            "bytes_per_site": 576},
    # bf16 bz=Z full-block admission: same logical bf16 bytes; the row
    # exists because the block schedule (one z-block, single-buffered
    # when the budget rejects double buffering) is a distinct kernel
    # configuration whose measured point must not silently drift into
    # the blocked-bf16 attribution
    "wilson_v2_bf16_bzfull": {"flops_per_site": 1320,
                              "bytes_per_site": 576},
    # int8 block-float links (r12f-style here+there reads, no resident
    # backward copy): mantissas 4 dirs x 9 complex x 2 x 1 B = 72 for
    # EACH of the here/there arrays + one f32 scale per (dir, site) x2
    # arrays = 2x16 + psi 5x96 + out 96 -> 72+72+16+16+480+96 = 752
    "wilson_v2_int8": {"flops_per_site": 1320, "bytes_per_site": 752},
    # sharded v2 interior (halo transport excluded from the model: it is
    # policy-dependent and O(surface); the trace carries the policy);
    # r12 variants mirror the single-chip subtraction
    "wilson_sharded_v2": {"flops_per_site": 1320, "bytes_per_site": 1152},
    "wilson_sharded_v2_r12": {"flops_per_site": 1320,
                              "bytes_per_site": 960},
    # XLA pair stencil: flop model only (XLA's fusion choices make a
    # static traffic model dishonest)
    "wilson_xla": {"flops_per_site": 1320, "bytes_per_site": None},
    # improved staggered fat+Naik two-pass gather kernel (PERF.md round
    # 8): per pass psi 5x24 + fwd links 288 + resident backward copy 288
    # + out 24 = 720, two passes + the XLA sum pass (2x24 read + 24
    # write)
    "staggered_fat_naik": {"flops_per_site": 1146,
                           "bytes_per_site": 1512},
    # plain staggered (fat hop set only): ONE gather pass, no sum pass
    "staggered_fat": {"flops_per_site": 570, "bytes_per_site": 720},
    # scatter-form (v3) staggered: no backward-link copies; per pass
    # psi 3x24 + links 288 + U_t plane 72 + out 24 = 456 (+ the sum
    # pass for the improved two-pass form)
    "staggered_fat_v3": {"flops_per_site": 570, "bytes_per_site": 456},
    "staggered_fat_naik_v3": {"flops_per_site": 1146,
                              "bytes_per_site": 984},
    # MRHS staggered, links amortized over N.  improved: the served
    # scatter two-pass body (models/staggered.served_forms) =
    # 2 passes x (psi 72 + out 24) + sum 72 + 1152/N links; fat-only:
    # one gather pass (psi 120 + out 24), no sum
    "staggered_mrhs": {"flops_per_site": 1146,
                       "bytes_per_site": lambda nrhs: 264.0
                       + 1152.0 / nrhs},
    "staggered_fat_mrhs": {"flops_per_site": 570,
                           "bytes_per_site": lambda nrhs: 144.0
                           + 576.0 / nrhs},
    # sharded staggered eo interiors (two-pass gather form — the mesh
    # default, models/staggered.py; halo transport excluded as for the
    # Wilson sharded rows: policy-dependent and O(surface))
    "staggered_sharded_fat": {"flops_per_site": 570,
                              "bytes_per_site": 720},
    "staggered_sharded_fat_naik": {"flops_per_site": 1146,
                                   "bytes_per_site": 1512},
    # XLA pair stencil: flop model only (same honesty rule as wilson_xla)
    "staggered_xla": {"flops_per_site": 1146, "bytes_per_site": None},
    # fused MG coarse-stencil kernel (ops/coarse_pallas.py) at the
    # CANONICAL probe size n_vec=4 (Nc=8, embedding dim E=16): 9 real
    # ExE matvecs = 18*E^2 flops/site; links once (36*E^2 B) + the
    # input and its 8 pre-rolled neighbour copies (36*E B) + out (4*E).
    # Nc-parametric attribution goes through
    # ops/coarse_pallas.coarse_model(nc) — this row is the drift-lint
    # anchor (obs/costmodel.py family 'mg_coarse')
    "mg_coarse_pallas": {"flops_per_site": 4608, "bytes_per_site": 9856},
    # -- operator-zoo fused forms (PERF.md round 18) --------------------
    # Clover PC fused kernel (ops/clover_pallas): per fused pass the v2
    # hop operand set (psi 5x96 + out 96 + fwd/bw links 2x288) plus the
    # resident chiral pair blocks streamed per tile — 2x6x6 complex f32
    # = 576 B/site (288 at bf16).  flops: hop 1320 + one 2x(6x6)
    # complex block matvec 504
    "clover_pallas": {"flops_per_site": 1824, "bytes_per_site": 1728},
    "clover_pallas_r12": {"flops_per_site": 1824,
                          "bytes_per_site": 1536},
    # MRHS fused clover: links AND blocks amortize over the RHS stream
    # (both index maps ignore n).  The route follows the shapes
    # (ops/clover_pallas.mrhs_route, PR 47); the row is what one
    # chip's 24^4 runs: full-Z tiles, one time-slice a step with the
    # 144 block planes resident — psi 3x96 + out 96 + (576+576)/N.
    # The z-blocked fallback of larger local volumes reads psi five
    # times (576 + 1152/N) and has no row of its own; the K2 stage's
    # ``xc`` (96 more) is outside this per-pass model on either route
    "clover_pallas_mrhs": {
        "flops_per_site": 1824,
        "bytes_per_site": lambda nrhs: 384.0 + 1152.0 / nrhs},
    # twisted mass: the twist is two STATIC scalars compiled into the
    # epilogue — zero extra traffic over the v2 hop; flops: hop 1320 +
    # twist rotate/combine 96
    "twisted_mass_pallas": {"flops_per_site": 1416,
                            "bytes_per_site": 1152},
    "twisted_mass_pallas_r12": {"flops_per_site": 1416,
                                "bytes_per_site": 960},
    # its batch has no blocks: the Wilson batch's route at 24^4, two
    # time-slices a step — psi 2x96 + out 96 + 576/N
    "twisted_mass_pallas_mrhs": {
        "flops_per_site": 1416,
        "bytes_per_site": lambda nrhs: 288.0 + 576.0 / nrhs},
    # twisted clover: dense block term (the twist is folded into the
    # inverse blocks / added in-register) — clover traffic and flops
    "twisted_clover_pallas": {"flops_per_site": 1824,
                              "bytes_per_site": 1728},
    "twisted_clover_pallas_r12": {"flops_per_site": 1824,
                                  "bytes_per_site": 1536},
    "twisted_clover_pallas_mrhs": {
        "flops_per_site": 1824,
        "bytes_per_site": lambda nrhs: 384.0 + 1152.0 / nrhs},
    # Ls-batched DWF/Möbius 4d hop (ops/dwf_pallas): per UPDATED 4d
    # site per dslash invocation with Ls baked in — Ls spinor planes
    # stream through ONE gauge-tile fetch (576) on the MRHS kernel's
    # full-Z route (two time-slices a step: psi read twice, 288 a
    # plane), i.e. 288 + 576/Ls per plane.  flops Ls x 1320.  Only Ls
    # in {4, 8} get traffic rows; other Ls report flops-only via
    # 'dwf_pallas'
    "dwf_ls4_pallas": {"flops_per_site": 5280, "bytes_per_site": 1728},
    "dwf_ls8_pallas": {"flops_per_site": 10560,
                       "bytes_per_site": 2880},
    # Ls outside the registered set: flops come from the operator
    # (flops_per_site override), no static traffic claim
    "dwf_pallas": {"flops_per_site": None, "bytes_per_site": None},
    # multi-source Möbius: N sources x Ls planes share one gauge tile;
    # bytes honesty as above (amortization shown by the bench row, not
    # a static model)
    "dwf_ls8_pallas_mrhs": {"flops_per_site": 10560,
                            "bytes_per_site": None},
    # staged XLA compositions: flop models only (same honesty rule as
    # wilson_xla — XLA's fusion choices make a traffic claim dishonest)
    "clover_xla": {"flops_per_site": 1824, "bytes_per_site": None},
    "twisted_xla": {"flops_per_site": 1416, "bytes_per_site": None},
    "twisted_clover_xla": {"flops_per_site": 1824,
                           "bytes_per_site": None},
    "dwf_xla": {"flops_per_site": None, "bytes_per_site": None},
    # operator-supplied flop count, no traffic model
    "generic": {"flops_per_site": None, "bytes_per_site": None},
}


def model(form: str, nrhs: int = 1, flops_per_site: Optional[float] = None
          ) -> tuple:
    """(flops_per_site, bytes_per_site or None) for a kernel form; a
    caller-supplied flops_per_site overrides (the 'generic' route)."""
    m = KERNEL_MODELS.get(form, KERNEL_MODELS["generic"])
    fps = m["flops_per_site"] if flops_per_site is None else flops_per_site
    bps = m["bytes_per_site"]
    if callable(bps):
        bps = bps(max(1, int(nrhs)))
    return fps, bps


def achieved(flops: float, bytes_: float, secs: float) -> dict:
    """Total flops/bytes + seconds -> {'gflops', 'gbps'} (rounded the
    way bench rows record them).  Non-positive seconds -> zeros: the
    bench gate rejects such rows; this helper must not divide by it."""
    if not (secs > 0):
        return {"gflops": 0.0, "gbps": 0.0}
    return {"gflops": round(flops / secs / 1e9, 2),
            "gbps": round(bytes_ / secs / 1e9, 2)}


def attribute(form: str, sites: int, applies: float, seconds: float,
              nrhs: int = 1, flops_per_site: Optional[float] = None,
              dslash_per_apply: float = 1.0,
              device_kind: Optional[str] = None, **extra) -> dict:
    """One roofline row: a kernel form applied ``applies`` times over
    ``sites`` updated sites (per RHS) in ``seconds`` wall.  The
    percent-of-peak columns use :func:`device_peaks` (``device_kind``
    default: the device present; None columns off-TPU).

    Units: ``flops_per_site`` (caller-supplied or the model's) is per
    APPLY per site, but ``bytes_per_site`` in KERNEL_MODELS is per
    DSLASH INVOCATION per site — a composite operator that runs several
    dslash per apply (the even/odd-preconditioned M is two) must pass
    ``dslash_per_apply`` so the traffic side is charged once per
    invocation; leaving it at 1 under-reports achieved BW by that
    factor.

    Returns {form, sites, applies, nrhs, seconds, flops, bytes,
    gflops, gbps, pct_peak_gflops, pct_peak_bw, **extra}; the bytes/BW
    columns are None for forms without a traffic model."""
    fps, bps = model(form, nrhs, flops_per_site)
    fps = float(fps or 0.0)
    flops = fps * sites * applies * max(1, int(nrhs))
    bts = (bps * sites * applies * dslash_per_apply * max(1, int(nrhs))
           if bps is not None else None)
    th = achieved(flops, bts or 0.0, seconds)
    peaks = device_peaks(device_kind)
    row = {"form": form, "sites": int(sites), "applies": float(applies),
           "nrhs": int(nrhs),
           "dslash_per_apply": float(dslash_per_apply),
           "seconds": round(float(seconds), 6),
           "flops_per_site": fps, "bytes_per_site": bps,
           "gflops": th["gflops"],
           "gbps": th["gbps"] if bts is not None else None,
           "pct_peak_gflops": (round(100.0 * th["gflops"]
                                     / peaks["gflops"], 2)
                               if peaks else None),
           "pct_peak_bw": (round(100.0 * th["gbps"] / peaks["gbps"], 2)
                           if peaks and bts is not None else None)}
    row.update(extra)
    return row


# -- per-process accumulation (flushed by end_quda) -------------------------

_rows: List[dict] = []
_dropped = 0
_MAX_ROWS = 10000
# the solve-service worker thread and the calling thread both record
# rows (the obs/memory lock discipline; a lost append is a silently
# thinner roofline.tsv)
_rows_lock = threading.Lock()


def record(form: str, sites: int, applies: float, seconds: float,
           nrhs: int = 1, flops_per_site: Optional[float] = None,
           dslash_per_apply: float = 1.0, **extra) -> dict:
    """attribute() + accumulate for the end_quda roofline.tsv dump +
    mirror as a trace event (auditable next to the spans it times)."""
    global _dropped
    row = attribute(form, sites, applies, seconds, nrhs=nrhs,
                    flops_per_site=flops_per_site,
                    dslash_per_apply=dslash_per_apply, **extra)
    with _rows_lock:
        if len(_rows) < _MAX_ROWS:
            _rows.append(row)
        else:
            # no silent caps (PERF.md round-9 rule): count what the tsv
            # will be missing so save() can mark the truncation
            _dropped += 1
    from . import trace as otr
    otr.event("roofline", cat="roofline", **row)
    return row


def rows() -> List[dict]:
    with _rows_lock:
        return list(_rows)


def reset():
    global _dropped
    with _rows_lock:
        _rows.clear()
        _dropped = 0


def save(fname: str = "roofline.tsv",
         path: Optional[str] = None) -> Optional[str]:
    """Dump accumulated rows as a tsv under ``path`` (default: the
    resource path — the profile_N.tsv sibling); None when no path or no
    rows.  The ICI attribution rows of the comms ledger (obs/comms.py
    ``attribute_solve``) are appended alongside the HBM rows: same
    form/seconds/gbps columns, percent column against the nominal ICI
    link bandwidth instead of the published HBM peak."""
    import os

    from . import comms as ocomms
    from ..utils import config as qconf
    path = path or qconf.get("QUDA_TPU_RESOURCE_PATH", fresh=True)
    ici_rows = ocomms.solve_rows()
    with _rows_lock:
        hbm_rows = list(_rows)
        dropped = _dropped
    if not path or not (hbm_rows or ici_rows):
        return None
    os.makedirs(path, exist_ok=True)
    cols = ("form", "sites", "applies", "nrhs", "seconds", "gflops",
            "gbps", "pct_peak_gflops", "pct_peak_bw", "label")
    out = os.path.join(path, fname)
    with open(out, "w") as fh:
        fh.write("\t".join(cols) + "\n")
        for r in hbm_rows:
            fh.write("\t".join(str(r.get(c, "")) for c in cols) + "\n")
        if dropped:
            fh.write(f"# TRUNCATED: {dropped} rows past the "
                     f"{_MAX_ROWS}-row cap were dropped\n")
        if ici_rows:
            fh.write(f"# ICI attribution (comms ledger; gbps = mesh-"
                     f"aggregate, pct = PER-DEVICE rate vs the nominal "
                     f"{ocomms.ICI_NOMINAL_GBPS:g} GB/s per-chip link, "
                     "NOT the HBM peak)\n")
            for r in ici_rows:
                fh.write("\t".join(str(v) for v in (
                    r["form"], r["ici_bytes"], r["applies"], "",
                    r["seconds"], "", r["gbps"], "",
                    r["pct_nominal_ici"],
                    f"{r['label']}|{r['policy']}|axes={r['axes']}"
                    f"|devices={r['devices']}")) + "\n")
    return out
