"""Where the Pallas kernels run: compiled on a TPU, interpreted on the CPU."""

from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """-> whether a kernel wrapper called with ``interpret=None`` runs the
    Pallas interpreter.  The TPU runs the compiled Mosaic kernel and the
    CPU (the test backend) interprets it; any other backend is an error,
    so a run that lost its chip never passes an interpreted kernel off
    as the compiled one."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels run compiled on 'tpu' or interpreted on "
        f"'cpu'; the default backend is {backend!r}")
