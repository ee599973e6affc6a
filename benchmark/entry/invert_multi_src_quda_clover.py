"""Entry point ``invert_multi_src_quda`` on the Wilson-clover operator
with the clover term resident: N spin-colour sources a call against one
loaded gauge field and one clover term (upstream's ``invert_test
--dslash-type clover --nsrc N``: ``loadCloverQuda`` once a
configuration, then ``invertMultiSrcQuda``; a Chroma-style propagator
campaign).  ``open`` is ``entry/invert_quda_clover.open`` (init,
``load_gauge_quda``, ``load_clover_quda``); ``call`` =
``invert_multi_src_quda``.  Interface as ``entry/invert_quda.py``; the
counters are the ``invert_multi_src_quda`` profile's phases and the
seconds of ``load_clover_quda``."""

from . import invert_multi_src_quda as multi
from . import invert_quda_clover as clover

# The guard: a commit without the resident batched route (the parent of
# PR 46) sends this batch down the per-call route, which rebuilds the
# canonical clover operators at 24^4 in every call and peaks at 14.07 of
# the chip's 15.75 GiB (27.5 s a call; my chip run, PR 46, PERF.md
# section 6).  Such a tree fails here, at import, in seconds, and is not
# taken that close to the chip's memory to find out.
from quda_tpu.interfaces.quda_api import (  # noqa: E402,F401
    _invert_clover_batch_resident)

open = clover.open
close = clover.close
call = multi.call


def counters():
    out = multi.counters()
    out["phase.clover_load"] = clover.counters()["phase.clover_load"]
    return out
