"""A footing on CPython's frame stack for what traces kernel bodies.

CPython (3.11 on) keeps a thread's interpreter frames on a stack of
16 KiB chunks and gives a chunk back to the allocator when its first
frame returns.  A loop whose calls straddle a chunk boundary maps and
unmaps a chunk a call, and tracing a kernel body is such a loop (some
10^4 equations, each bound a dozen frames down): the same trace takes
0.7 s or 1.8 here, 0.8 or 6-9 s on the chip's host (PERF.md section 7
(22)), by where on that stack the caller stands, which any local
variable more in any frame above it moves.
"""

from __future__ import annotations


def big_frame_caller(n_locals: int = 4200):
    """``call(fn) -> fn()`` from a frame of ``n_locals`` locals.

    A frame too large for what is left of any 16 KiB chunk always opens
    a chunk of its own (64 KiB for 4,200 locals, 30 of them free below
    it), so what runs under it stands at the same place whoever
    calls."""
    names = " = ".join(f"_{i}" for i in range(n_locals))
    scope = {}
    exec(f"def call(fn):\n    {names} = None\n    return fn()\n", scope)
    return scope["call"]


on_a_stack_chunk_of_its_own = big_frame_caller()
