"""What the platform decides, in one place: whether Pallas kernels run
compiled (on a TPU) or in interpret mode (everywhere else, e.g. CPU test
runs)."""
from __future__ import annotations


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: on every platform but
    a TPU.  Every kernel entry point whose ``interpret`` is left at
    ``None`` asks here, so on a TPU the compiled kernel runs and nothing
    silently interprets it."""
    import jax
    return jax.default_backend() != "tpu"
