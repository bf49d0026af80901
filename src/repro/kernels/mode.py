"""The one rule for when a Pallas kernel runs in interpret mode.

Interpret mode executes a kernel body through the Pallas interpreter on
the host: exact block/grid semantics at Python speed.  That is what the
CPU parity tests need, and what a chip must never run.  The rule is
evaluated while the calling jit traces, never at import, so importing a
kernel module touches no device state.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``None`` means: interpret on the ``cpu`` backend, compile elsewhere.

    An explicit ``True`` on a ``tpu`` backend raises, so no path on the
    chip can fall back to the interpreter.  An explicit ``False`` is
    honoured on any backend; a CPU process passes it to compile a kernel
    for a described TPU (``tests/test_tpu_compile.py``).
    """
    backend = jax.default_backend()
    if interpret is None:
        return backend == "cpu"
    if interpret and backend == "tpu":
        raise ValueError("Pallas interpret mode was requested on a TPU "
                         "backend; kernels on the chip run compiled")
    return interpret
