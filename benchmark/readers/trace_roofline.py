"""A kernel's share of its memory roofline, in percent: the bytes the
algorithm NEEDS for the traced calls of the kernel (``kernel_models/
<model>.py``, from shapes and the element types each traced call really
had), over the published HBM bandwidth of this device kind
(``peaks.py``), over the device time the trace shows for the same calls.
``rhs_from_config`` names the configuration key that holds how many
sources stream through one call of the kernel."""

import importlib
import re

from .. import peaks

BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1}
_SIG = re.compile(r" (\w+)<-(\w+),(\w+)$")


def read(ctx, pattern, model, rhs_from_config=None):
    if ctx["trace"] is None:
        return None
    rx = re.compile(pattern)
    needed = importlib.import_module(
        f"{ctx['package']}.kernel_models.{model}").needed
    n_rhs = int(ctx["config"][rhs_from_config]) if rhs_from_config else 1
    bw = peaks.peak(ctx["device_kind"], "hbm_bytes_per_s")
    floor_s = seconds = 0.0
    for name, k in ctx["trace"]["kernels"].items():
        sig = _SIG.search(name)
        if not (rx.search(name) and sig):
            continue
        out_b, in_b, link_b = (BYTES[t] for t in sig.groups())
        need = needed(ctx["lattice"], link_bytes=link_b, in_bytes=in_b,
                      out_bytes=out_b, n_rhs=n_rhs)
        floor_s += k["count"] * need["bytes"] / bw
        seconds += k["seconds"]
    if seconds <= 0:
        return None
    return 100.0 * floor_s / seconds
