"""Where a Pallas kernel runs: compiled on a TPU, interpreted on the CPU.

The choice is made when the program is lowered, from the platform it is
lowered for, so it needs no flag: a CPU process runs the interpreter, a TPU
process runs the Mosaic kernel, and an ahead-of-time compile for a described
TPU (``tests/test_tpu_compile.py``) gets the Mosaic kernel too.
"""

from __future__ import annotations

import functools

import jax


def pallas_on_platform(call, *args):
    """``call(*args, interpret=...)`` with ``interpret`` true only on CPU."""
    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(call, interpret=True),
        default=functools.partial(call, interpret=False))
