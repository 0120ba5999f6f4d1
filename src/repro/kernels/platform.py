"""Where a Pallas kernel is compiled and where it is interpreted.

Every ``backend="pallas"`` wrapper calls its kernel through
:func:`compiled_on_tpu`, so the choice lives in this one place and is
made from the platform the program is LOWERED for: Mosaic-compiled on a
TPU, the Pallas interpreter on every other backend (the CPU the tests
run on).  A program built for a TPU therefore never carries an
interpreted kernel, and no caller passes an ``interpret`` flag.
"""

from __future__ import annotations

import functools

import jax


def compiled_on_tpu(kernel, *args):
    """``kernel(*args, interpret=...)`` — ``interpret=False`` when lowered
    for a TPU, ``True`` elsewhere (``lax.platform_dependent`` lowers only
    the branch of the target platform)."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, interpret=False),
        default=functools.partial(kernel, interpret=True))
