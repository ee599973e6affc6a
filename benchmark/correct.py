"""The comparison that decides ``correct``.

What the timed path produced — solutions of the window's own calls, at
the timed size — is held against the plain reference operator: for every
source of every sampled call, ``||b - M x|| / ||b||`` under
``reference/<family>.py``.  Two numbers are compared, each with a limit
of its own from the traffic file:

* ``res_max``: the largest checked residual; limit ``res_bound`` (the
  configuration's guarantee);
* ``agree_max``: the largest ``|true_res - checked| / checked`` between
  the API's own verified-exit residual and the check; limit
  ``agree_bound`` (a program whose epilogue operator drops precision
  reports a residual the reference does not confirm).

A run is correct when both hold, nothing was non-finite, and no source
of the window failed by the API's own report.
"""

import math


def compare(reference, links, kappa, nx, samples, traffic, out=print):
    """``samples``: [{"label", "sources", "solutions", "true_res"}] with
    fields in the reference layout (n, 4, 3, T, Z, Y*X); ``nx`` the x extent.  Returns
    {"correct", "numbers": [{"name", "value", "limit"}], "checked"}."""
    res, agree = [], []
    for s in samples:
        for i in range(len(s["true_res"])):
            r = reference.rel_residual(links, kappa, nx, s["sources"][i],
                                       s["solutions"][i])
            api = float(s["true_res"][i])
            a = abs(api - r) / r if r > 0 else math.inf
            res.append(r)
            agree.append(a)
            out(f"check {s['label']} source {i}: checked_res {r:.6e} "
                f"api_true_res {api:.6e} agree {a:.3e}")
    numbers = [
        {"name": "res_max", "value": max(res, default=math.inf),
         "limit": float(traffic["res_bound"])},
        {"name": "agree_max", "value": max(agree, default=math.inf),
         "limit": float(traffic["agree_bound"])},
    ]
    ok = True
    for n in numbers:
        good = math.isfinite(n["value"]) and n["value"] <= n["limit"]
        ok = ok and good
        out(f"compare {n['name']}: value {n['value']:.6e} limit "
            f"{n['limit']:.6e} {'ok' if good else 'OVER'}")
    return {"correct": ok, "numbers": numbers, "checked": len(res)}
