"""Public wrapper for the replica-strategy plan pass (pallas / interpret /
numpy).

Like ``st_cost``, this op is called from host code (the batched planner
classes in :mod:`repro.core.replica`, once per arrival burst and per
singleton replan), so it takes and returns host numpy values and picks
the route per call:

  * ``"auto"``   — the compiled Pallas kernel on TPU; the float64 numpy
    oracle on CPU (no per-burst jax dispatch overhead, bit-identical to
    the oracle trivially). This is what ``strategy_mode="batch"`` uses.
  * ``"pallas"`` — force the compiled kernel. Compiled TPU execution is
    float32 (no f64 on TPU): site picks can drift on near-tie effective
    bandwidths, so the bit-identity contract covers the CPU routes only.
  * ``"interpret"`` — the kernel under the Pallas interpreter with x64
    enabled: slow, bit-identical to the oracle; used by the kernel tests.
  * ``"numpy"``  — the oracle directly.
"""

from __future__ import annotations

import numpy as np

from .ref import strategy_plan_ref


def strategy_plan(bw, fetch, local, serve, free, size, *,
                  backend: str = "auto"
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                             np.ndarray, np.ndarray]:
    """Plan one burst of (job, missing-file) pairs.

    See :func:`.ref.strategy_plan_ref` for the argument contract.
    Returns host ``(src_global, src_local, has_local, inter_global,
    store_ok)`` with decision dtypes (``intp`` site ids, ``bool`` flags)
    regardless of backend.
    """
    if backend != "numpy":
        import jax  # deferred: the oracle route needs no jax

        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "numpy"
    if backend in ("pallas", "interpret"):
        from .kernel import strategy_plan_kernel
        interpret = backend == "interpret"
        dtype = np.float64 if interpret else np.float32
        with jax.enable_x64(interpret):
            out = strategy_plan_kernel(
                *(np.asarray(a, dtype) for a in (bw, fetch, local, serve,
                                                 free, size)),
                interpret=interpret)
        return _decisions(*(np.asarray(o, np.float64) for o in out))
    if backend != "numpy":
        raise ValueError(f"unknown strategy_plan backend {backend!r} "
                         "(want 'auto'|'pallas'|'interpret'|'numpy')")
    return _decisions(*strategy_plan_ref(bw, fetch, local, serve, free,
                                         size))


def _decisions(src_g, src_l, has_l, inter_g, store_ok):
    """Float kernel outputs -> host decision dtypes."""
    return (src_g.astype(np.intp), src_l.astype(np.intp),
            has_l > 0.0, inter_g > 0.0, store_ok > 0.0)
