"""Public parameter structs — the QudaGaugeParam/QudaInvertParam/... analog.

Reference behavior: include/quda.h:31-871 param structs with generated
default-init/validation/printing from lib/check_params.h X-macros.
Python dataclasses give the same three operations natively: defaults in
field definitions, validate() for CHECK_PARAM, describe() for PRINT_PARAM.
Enum strings follow include/enum_quda.h spellings, lowercased.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

# enum value sets (enum_quda.h analogs)
DSLASH_TYPES = ("wilson", "clover", "twisted-mass", "twisted-clover",
                "ndeg-twisted-mass", "ndeg-twisted-clover", "staggered",
                "asqtad", "hisq", "domain-wall", "domain-wall-4d", "mobius",
                "mobius-eofa", "laplace")
INVERTER_TYPES = ("cg", "cg3", "cgne", "cgnr", "pcg", "bicgstab",
                  "bicgstab-l", "gcr", "mr", "sd", "ca-cg", "ca-gcr",
                  "multi-shift-cg", "gcr-mg")
PRECISIONS = ("double", "single", "half", "quarter")
MATPC_TYPES = ("even-even", "odd-odd")
SOLUTION_TYPES = ("mat", "matpc", "matdag-mat", "matpc-dag-matpc")
SOLVE_TYPES = ("direct", "direct-pc", "normop", "normop-pc")


def _check(cond, msg):
    if not cond:
        from ..utils.logging import errorq
        errorq(msg)


@dataclasses.dataclass
class GaugeParam:
    """QudaGaugeParam (quda.h:31)."""
    X: Tuple[int, int, int, int] = (8, 8, 8, 8)   # (x,y,z,t)
    t_boundary: str = "antiperiodic"               # periodic|antiperiodic
    cpu_prec: str = "double"
    cuda_prec: str = "double"                      # device precision
    # host layout of the array passed to load_gauge_quda
    # (QudaGaugeFieldOrder: canonical | qdp | milc | cps)
    gauge_order: str = "canonical"
    reconstruct: int = 18
    anisotropy: float = 1.0
    tadpole_coeff: float = 1.0
    staggered_phase_type: str = "milc"
    make_resident_gauge: bool = True

    def validate(self):
        _check(len(self.X) == 4 and all(d > 0 for d in self.X),
               f"bad lattice dims {self.X}")
        _check(self.t_boundary in ("periodic", "antiperiodic"),
               f"bad t_boundary {self.t_boundary}")
        _check(self.cuda_prec in PRECISIONS, f"bad prec {self.cuda_prec}")
        _check(self.gauge_order in ("canonical", "qdp", "milc", "cps"),
               f"bad gauge_order {self.gauge_order}")
        return self

    def describe(self) -> str:
        return "\n".join(f"{f.name} = {getattr(self, f.name)}"
                         for f in dataclasses.fields(self))


@dataclasses.dataclass
class InvertParam:
    """QudaInvertParam (quda.h:100)."""
    dslash_type: str = "wilson"
    inv_type: str = "cg"
    solution_type: str = "mat"
    solve_type: str = "normop-pc"
    matpc_type: str = "even-even"
    mass: float = -0.9
    kappa: float = 0.12
    mu: float = 0.0
    epsilon: float = 0.0
    csw: float = 0.0
    m5: float = -1.8                  # domain wall height (QUDA sign conv.)
    Ls: int = 8
    b5: float = 1.5
    c5: float = 0.5
    # EOFA (QudaInvertParam eofa_pm/eofa_shift/mq1-3, quda.h)
    eofa_pm: bool = True
    eofa_shift: float = 0.0
    eofa_mq1: float = None
    eofa_mq2: float = None
    eofa_mq3: float = None
    laplace3D: int = 3
    tol: float = 1e-10
    tol_hq: float = 0.0
    maxiter: int = 10000
    reliable_delta: float = 0.1
    pipeline: int = 0
    num_offset: int = 0               # multi-shift
    offset: Sequence[float] = ()
    # per-shift tolerances (QUDA's tol_offset[]): accepted only empty
    # or equal to ``tol`` for every shift; anything else is refused
    tol_offset: Sequence[float] = ()
    cuda_prec: str = "double"
    # "auto" resolves at solve time: bf16 ("half") on TPU, = cuda_prec on
    # CPU.  Pinning any explicit value opts out of the TPU default.
    cuda_prec_sloppy: str = "auto"
    cuda_prec_precondition: str = "half"
    gcrNkrylov: int = 16
    verbosity: str = "summarize"
    # results (returned)
    true_res: float = 0.0
    iter_count: int = 0
    secs: float = 0.0
    gflops: float = 0.0
    # multi-source results (invert_multi_src_quda): per-RHS true
    # residuals and per-RHS iteration counts (QUDA's per-source
    # true_res[] array on QudaInvertParam); iter_count/gflops then hold
    # the per-RHS sums with the volume/2 PC flop convention
    true_res_multi: Sequence[float] = ()
    iter_count_multi: Sequence[int] = ()
    # multi-shift results (invert_multishift_quda; QUDA's
    # true_res_offset[] / iter_res_offset[]): per shift the true
    # residual |b - (A + offset_i) x_i| / |b| recomputed at the exit,
    # and the loop's own analytic zeta_i |r| / |b|; ``true_res`` stays
    # shift 0's as in QUDA, ``converged_multi`` is per shift;
    # ``iter_count_offset`` the iterations each shift was updated in
    # (a converged shift leaves the update; shift 0's is ``iter_count``)
    true_res_offset: Sequence[float] = ()
    iter_res_offset: Sequence[float] = ()
    iter_count_offset: Sequence[int] = ()
    # convergence trace (populated when QUDA_TPU_TRACE is on —
    # obs/convergence.py): res_history = per-check-point entries
    # [{"iter", "r2", "relres"}, ...] (every iteration at cadence 1),
    # events = reliable_update / restart / breakdown / shift_converged /
    # cadence markers.  Empty on untraced solves (zero-overhead path).
    res_history: Sequence = ()
    events: Sequence = ()
    # solve supervision (quda_tpu/robust): ``converged`` is ALWAYS
    # maintained — a solve that exits at maxiter without meeting tol
    # reports False (and warns once) instead of silently returning an
    # unconverged answer; ``converged_multi`` is its per-RHS/per-shift
    # form.  With QUDA_TPU_ROBUST != off, ``verified_res`` holds the
    # true residual recomputed with the hi-precision XLA reference
    # operator at the API boundary, ``solve_status`` classifies the
    # exit ('converged' / 'unconverged' / 'breakdown:<reason>' /
    # 'unverified' / 'degraded:<status>'), and ``solve_attempts``
    # carries the escalation ladder's per-attempt provenance
    # (robust/escalate.py).
    converged: bool = True
    converged_multi: Sequence = ()
    verified_res: float = 0.0
    solve_status: str = ""
    solve_attempts: Sequence = ()

    def validate(self):
        _check(self.dslash_type in DSLASH_TYPES,
               f"unknown dslash_type {self.dslash_type}")
        _check(self.inv_type in INVERTER_TYPES,
               f"unknown inv_type {self.inv_type}")
        _check(self.solve_type in SOLVE_TYPES,
               f"unknown solve_type {self.solve_type}")
        _check(self.matpc_type in MATPC_TYPES,
               f"unknown matpc_type {self.matpc_type}")
        _check(self.tol > 0 and self.maxiter > 0, "bad tol/maxiter")
        if self.num_offset:
            _check(len(self.offset) == self.num_offset, "offset mismatch")
        if self.offset:
            _check(min(self.offset) >= self.offset[0],
                   f"offset[0] must be the smallest shift (QUDA takes "
                   f"them ascending), got {tuple(self.offset)}")
        if len(self.tol_offset):
            _check(len(self.tol_offset) == len(self.offset)
                   and all(float(t) == float(self.tol)
                           for t in self.tol_offset),
                   f"tol_offset {tuple(self.tol_offset)}: per-shift "
                   f"tolerances other than tol ({self.tol:g}) are not "
                   "served")
        return self

    def describe(self) -> str:
        return "\n".join(f"{f.name} = {getattr(self, f.name)}"
                         for f in dataclasses.fields(self))


@dataclasses.dataclass
class EigParamAPI:
    """QudaEigParam (quda.h:471)."""
    eig_type: str = "trlm"            # trlm | iram
    n_ev: int = 8
    n_kr: int = 32
    tol: float = 1e-8
    max_restarts: int = 100
    spectrum: str = "SR"
    use_poly_acc: bool = False
    poly_deg: int = 20
    a_min: float = 0.1
    a_max: float = 4.0
    use_norm_op: bool = True          # solve on MdagM
    use_dagger: bool = False
    vec_outfile: str = ""
    vec_infile: str = ""

    def validate(self):
        _check(self.eig_type in ("trlm", "iram", "arpack"),
               "bad eig_type")
        _check(0 < self.n_ev < self.n_kr, "need n_ev < n_kr")
        return self


@dataclasses.dataclass
class MultigridParamAPI:
    """QudaMultigridParam (quda.h:616), per-level lists."""
    n_level: int = 2
    geo_block_size: Sequence[Tuple[int, int, int, int]] = ((2, 2, 2, 2),)
    n_vec: Sequence[int] = (8,)
    setup_iters: Sequence[int] = (150,)
    # null-vector solve tolerance per level (QudaMultigridParam::
    # setup_tol): the MRHS setup solve stops at |r| <= tol*|b| with
    # setup_iters as the cap; ignored by QUDA_TPU_MG_SETUP=legacy
    setup_tol: Sequence[float] = (5e-6,)
    nu_pre: Sequence[int] = (0,)
    nu_post: Sequence[int] = (4,)
    smoother_omega: float = 0.85
    coarse_solver_iters: int = 8
    vec_outfile: str = ""
    vec_infile: str = ""

    def validate(self):
        n = self.n_level - 1
        _check(len(self.geo_block_size) >= n, "need block size per level")
        _check(len(self.n_vec) >= n, "need n_vec per level")
        return self
