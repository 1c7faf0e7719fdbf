"""Public wrapper for the value-scoring pass (pallas / interpret / numpy).

This op is called from host code (the economy's periodic DES event),
so it returns host numpy values and picks the route per call:

  * ``"auto"``   — the compiled Pallas kernel on TPU; the float64 numpy
    oracle on CPU (no per-event jax dispatch overhead, bit-identical to
    the oracle trivially). This is what ``econ="pallas"`` uses.
  * ``"pallas"`` — force the compiled kernel. Compiled TPU execution is
    float32 (no f64 on TPU): ~1e-7 relative drift vs the oracle, so the
    bit-identity contract covers the CPU routes only.
  * ``"interpret"`` — the kernel under the Pallas interpreter with x64
    enabled: slow, bit-identical to the oracle; used by the kernel tests
    and the ``econ="pallas-interpret"`` engine flag.
  * ``"numpy"``  — the oracle directly.
"""

from __future__ import annotations

import numpy as np

from .ref import MODES, value_score_ref


def value_score(demand, sizes, presence, bw, *, mode: str = "cost",
                backend: str = "auto") -> np.ndarray:
    """Score the full (sites, files) replica value matrix.

    See :func:`.ref.value_score_ref` for the argument contract. Returns a
    host float64 array regardless of backend.
    """
    if mode not in MODES:
        raise ValueError(f"unknown value_score mode {mode!r} "
                         f"(want one of {MODES})")
    if backend != "numpy":
        import jax  # deferred: the oracle route needs no jax

        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "numpy"
    if backend in ("pallas", "interpret"):
        from .kernel import value_score_kernel
        interpret = backend == "interpret"
        dtype = np.float64 if interpret else np.float32
        with jax.enable_x64(interpret):
            out = value_score_kernel(
                np.asarray(demand, dtype), np.asarray(sizes, dtype),
                np.asarray(presence, dtype), np.asarray(bw, dtype),
                mode=mode, interpret=interpret)
        return np.asarray(out, np.float64)
    if backend != "numpy":
        raise ValueError(f"unknown value_score backend {backend!r} "
                         "(want 'auto'|'pallas'|'interpret'|'numpy')")
    return value_score_ref(demand, sizes, presence, bw, mode=mode)
