"""Public wrapper for the shortest-transfer cost pass (pallas / interpret /
numpy).

Like ``value_score``, this op is called from host code (the jitted
``shortesttransfer`` broker, once per dispatch batch), so it takes and
returns host numpy values and picks the route per call:

  * ``"auto"``   — the compiled Pallas kernel on TPU; the float64 numpy
    oracle on CPU (no per-batch jax dispatch overhead, bit-identical to
    the oracle trivially). This is what the broker uses.
  * ``"pallas"`` — force the compiled kernel. Compiled TPU execution is
    float32 (no f64 on TPU): ~1e-7 relative drift vs the oracle, so the
    bit-identity contract covers the CPU routes only.
  * ``"interpret"`` — the kernel under the Pallas interpreter with x64
    enabled: slow, bit-identical to the oracle; used by the kernel tests.
  * ``"numpy"``  — the oracle directly.
"""

from __future__ import annotations

import numpy as np

from .ref import st_cost_ref


def st_cost(bw, fetch_mask, presence, sizes, required, rel, online, *,
            backend: str = "auto") -> np.ndarray:
    """Cost the full (jobs, sites) dispatch matrix of one batch.

    See :func:`.ref.st_cost_ref` for the argument contract. Returns a
    host float64 array regardless of backend.
    """
    if backend != "numpy":
        import jax  # deferred: the oracle route needs no jax

        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "numpy"
    if backend in ("pallas", "interpret"):
        from .kernel import st_cost_kernel
        interpret = backend == "interpret"
        dtype = np.float64 if interpret else np.float32
        with jax.enable_x64(interpret):
            out = st_cost_kernel(
                *(np.asarray(a, dtype) for a in (bw, fetch_mask, presence,
                                                 sizes, required, rel,
                                                 online)),
                interpret=interpret)
        return np.asarray(out, np.float64)
    if backend != "numpy":
        raise ValueError(f"unknown st_cost backend {backend!r} "
                         "(want 'auto'|'pallas'|'interpret'|'numpy')")
    return st_cost_ref(bw, fetch_mask, presence, sizes, required, rel,
                       online)
