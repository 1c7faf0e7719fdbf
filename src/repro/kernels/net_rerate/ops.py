"""Public wrapper for the net re-rate (pallas / interpret / numpy ref).

Unlike the model kernels this op is called from the discrete-event loop
(host code, once per link-occupancy change), so the wrapper returns host
numpy values and picks the backend per call:

  * ``"auto"``   — the compiled Pallas kernel on TPU; the float64 numpy
    oracle on CPU (no per-event jax dispatch overhead, bit-identical to
    the incremental engine backend). This is what ``net="pallas"`` uses.
  * ``"pallas"`` — force the compiled kernel. Compiled TPU execution is
    float32 (no f64 on TPU): ~1e-7 relative rate drift vs the oracle, so
    the engine's bit-identity contract covers the CPU routes only.
  * ``"interpret"`` — the kernel under the Pallas interpreter with x64
    enabled: slow, but bit-identical to the oracle; used by the kernel
    tests and the ``net="pallas-interpret"`` engine flag.
  * ``"numpy"``  — the oracle directly.
"""

from __future__ import annotations

import numpy as np

from .ref import net_rerate_ref


def net_rerate(path, rem, link_bw, link_act, now, *, backend: str = "auto"
               ) -> tuple[np.ndarray, float]:
    """Re-rate transfer slots and scan for the next completion.

    See :func:`.ref.net_rerate_ref` for the argument contract. Returns a
    host ``(rate, eta)`` pair regardless of backend. The kernel routes
    scan relative to ``now`` and add it back on the host in float64, as
    :func:`repro.kernels.event_engine.event_engine` does (exact in
    float64, so the interpret route stays bit-identical to the oracle).
    """
    if backend != "numpy":
        import jax  # deferred: the oracle route needs no jax

        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "numpy"
    if backend in ("pallas", "interpret"):
        from .kernel import net_rerate_kernel
        interpret = backend == "interpret"
        dtype = np.float64 if interpret else np.float32
        with jax.enable_x64(interpret):
            rate, eta = net_rerate_kernel(
                np.asarray(path, np.int32), np.asarray(rem, dtype),
                np.asarray(link_bw, dtype), np.asarray(link_act, dtype),
                dtype(0.0), interpret=interpret)
        return np.asarray(rate, np.float64), now + float(eta)
    if backend != "numpy":
        raise ValueError(f"unknown net_rerate backend {backend!r} "
                         "(want 'auto'|'pallas'|'interpret'|'numpy')")
    return net_rerate_ref(path, rem, link_bw, link_act, now)
