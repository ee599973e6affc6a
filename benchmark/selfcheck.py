#!/usr/bin/env python3
"""The yardstick checks itself (CPU, seconds):

1. the plain reference agrees with the program's ``DiracWilson.M`` at
   4^4 (and on a lattice with four different extents) to 1e-6;
2. the trace reduction gives the known busy time, kernel time and
   longest gap on the small recorded trace in ``fixtures/``;
3. the needed-bytes count gives 768 B per output site in f32;
4. every file ``BENCHMARK.json`` names loads, every metric has its file
   and its reader, every cell its configuration, traffic and entry.

    JAX_PLATFORMS=cpu python3 benchmark/selfcheck.py
"""

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
PKG = os.path.basename(HERE)


def check_reference():
    import jax.numpy as jnp

    from quda_tpu.fields.geometry import LatticeGeometry
    from quda_tpu.models.wilson import DiracWilson
    data = importlib.import_module(f"{PKG}.data")
    ref = importlib.import_module(f"{PKG}.reference.wilson")
    for lat in ((4, 4, 4, 4), (4, 6, 2, 8)):          # T, Z, Y, X
        u = data.su3_field(data.key_of(2 ** 31 + 5, 0), (4,), lat, 0.7)
        psi = data.gaussian_sources(data.key_of(7, 1), lat, 1)
        d = DiracWilson(data.to_canonical_gauge(u, lat),
                        LatticeGeometry(tuple(reversed(lat))), 0.124,
                        antiperiodic_t=True)
        prog = d.M(data.to_canonical_spinors(psi, lat)[0])
        mine = ref.apply_m(ref.fold_boundary(u, True), psi[0], 0.124,
                           lat[3])
        mine = data.to_canonical_spinors(mine[None], lat)[0]
        diff = float(jnp.linalg.norm((prog - mine).ravel())
                     / jnp.linalg.norm(mine.ravel()))
        assert diff < 1e-6, (lat, diff)
        print(f"reference vs DiracWilson.M at {lat}: rel diff {diff:.2e}")
        # the seed's gauge rotation is a symmetry: M[U'] (g psi) = g (M[U] psi)
        g = data.su3_field(data.key_of(11, 1), (), lat, 1.0)
        rot = lambda v: sum(g[:, b][None, :] * v[:, b][:, None]
                            for b in range(3))
        lhs = ref.apply_m(ref.fold_boundary(
            data.gauge_rotate(u, g, lat[3]), True), rot(psi[0]), 0.124,
            lat[3])
        rhs = rot(ref.apply_m(ref.fold_boundary(u, True), psi[0], 0.124,
                              lat[3]))
        cov = float(jnp.linalg.norm((lhs - rhs).ravel())
                    / jnp.linalg.norm(rhs.ravel()))
        assert cov < 1e-5, (lat, cov)
        print(f"gauge rotation is a symmetry of M at {lat}: {cov:.2e}")


def check_trace_reduction():
    tr = importlib.import_module(f"{PKG}.trace_reduce")
    for name in sorted(os.listdir(os.path.join(HERE, "fixtures"))):
        with open(os.path.join(HERE, "fixtures", name)) as fh:
            fx = json.load(fh)
        red = tr.reduce(fx["trace"])
        exp = fx["expected"]
        assert abs(red["window_s"] - exp["window_s"]) < 1e-9, red
        assert abs(red["busy_s"] - exp["busy_s"]) < 1e-9, red
        assert abs(red["idle_gaps"][0][1] - exp["longest_gap_s"]) < 1e-9
        for pattern, (count, seconds) in exp["kernels"].items():
            c, s = tr.kernel_time(red, pattern)
            assert c == count and abs(s - seconds) < 1e-9, (pattern, c, s)
        print(f"trace reduction on fixtures/{name}: busy "
              f"{red['busy_s']:.6f} s of {red['window_s']:.6f} s, "
              f"{len(exp['kernels'])} kernel sums as recorded")


def check_needed_bytes():
    km = importlib.import_module(f"{PKG}.kernel_models.wilson_eo_dslash")
    n = km.needed((24, 24, 24, 24))
    assert n["bytes_per_site"] == 768 and n["sites"] == 165888, n
    assert km.needed((24,) * 4, link_bytes=2, in_bytes=2,
                     out_bytes=2)["bytes_per_site"] == 384
    assert km.needed((24,) * 4, n_rhs=8)["bytes_per_site"] == 576 + 8 * 192
    tr = importlib.import_module(f"{PKG}.trace_reduce")
    hlo = ('%dslash_eo_pallas_packed.25 = f32[4,3,2,24,24,288]{5,4:T(8,128)}'
           ' custom-call(bf16[4,3,2,24,24,288]{5,4} %a, bf16[4,3,3,2,24,24,'
           '288]{6,5} %u), custom_call_target="tpu_custom_call", x={}')
    assert tr.short_name(hlo) == "dslash_eo_pallas_packed.25 f32<-bf16,bf16"
    assert tr.short_name("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %p)") \
        == "fusion.7"
    print("needed bytes: 768 B per output site f32, 165,888 sites at 24^4")


def check_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    configs = {}
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as fh:
            configs[c["name"]] = cfg = json.load(fh)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"], c["name"]
        importlib.import_module(f"{PKG}.entry.{cfg['entry']}")
        importlib.import_module(f"{PKG}.reference.{cfg['reference']}")
    for w in bench["workloads"]:
        assert w["config"] in configs, w
        assert w["chips"] == configs[w["config"]]["chips"], w
        with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as fh:
            t = json.load(fh)
        assert 0 < t["res_bound"] <= 1e-4, t     # PERF.md: why 1e-4
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for section in ("end_to_end", "per_layer"):
        for m in bench[section]:
            with open(os.path.join(HERE, section, m["name"] + ".json")) as fh:
                spec = json.load(fh)
            reader = importlib.import_module(
                f"{PKG}.readers.{spec['reader']}")
            assert callable(reader.read), spec
            if "model" in spec.get("args", {}):
                importlib.import_module(
                    f"{PKG}.kernel_models.{spec['args']['model']}")
            assert set(m.get("workloads", cells)) <= cells, m
            assert section == "end_to_end" or m["moves"] in e2e, m
    print(f"BENCHMARK.json: {len(configs)} configurations, {len(cells)} "
          f"cells, {len(bench['end_to_end'])} + {len(bench['per_layer'])} "
          "metrics, every file loads")


if __name__ == "__main__":
    check_needed_bytes()
    check_files()
    check_trace_reduction()
    check_reference()
    print("selfcheck: ok")
