"""Pallas kernels (GEMM, flash attention) and their record-aware dispatch.

A kernel runs compiled natively on a TPU and in Pallas' interpreter
everywhere else; the interpreter on a TPU backend would time a Python
emulation in place of the kernel, so it is refused there.
"""

import jax


def interpret_default() -> bool:
    """Whether Pallas kernels run in the interpreter: exactly when the
    backend is not a TPU (the CPU tests)."""
    return jax.default_backend() != "tpu"


def check_interpret(interpret: bool) -> None:
    """Refuse interpret mode on a TPU backend."""
    if interpret and not interpret_default():
        raise ValueError(
            "interpret=True on a TPU backend runs the Pallas interpreter in "
            "place of the compiled kernel; pass interpret=False"
        )
