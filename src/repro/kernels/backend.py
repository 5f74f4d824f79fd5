"""Pallas execution-mode resolution shared by every kernel entry point.

The kernels take ``interpret: bool | None``.  ``None`` (the default)
compiles through Mosaic when the default JAX backend is a TPU and runs
the Pallas interpreter on the CPU, where the tests run.  The interpreter
never runs on a TPU: it is orders of magnitude slower than the compiled
kernel and fails nothing, so a TPU run in interpret mode would measure
the interpreter while looking healthy.  ``interpret=False`` off the TPU
is what the compile rehearsals use (a kernel compiled for a described,
unattached chip).
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Concrete interpret flag for a ``pl.pallas_call``.

    Called at trace time (``interpret`` is a static argument of every
    kernel's jit wrapper), so the backend probe costs nothing per step.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret mode is for the CPU; on a TPU the "
                         "kernels always compile")
    return bool(interpret)
