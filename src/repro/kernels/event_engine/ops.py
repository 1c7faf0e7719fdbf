"""Public wrapper for the event-engine flush (pallas / interpret / numpy).

Like :mod:`repro.kernels.net_rerate`, this op is called from the
discrete-event loop (host code, once per drained event instant), so the
wrapper returns host numpy values and picks the backend per call:

  * ``"auto"``   — the compiled Pallas kernel on TPU; the float64 numpy
    oracle on CPU (no per-instant jax dispatch overhead). This is what
    ``net="device"`` uses.
  * ``"pallas"`` — force the compiled kernel. Compiled TPU execution is
    float32 (no f64 on TPU): extra ~1e-7 relative drift on top of the
    reconstruction drift the tolerance goldens already bound.
  * ``"interpret"`` — the kernel under the Pallas interpreter with x64
    enabled: slow, but bit-identical to the oracle; used by the kernel
    tests and the ``net="device-interpret"`` engine flag.
  * ``"numpy"``  — the oracle directly.
"""

from __future__ import annotations

import numpy as np

from .ref import event_engine_ref


def event_engine(path, rem, rate, eta, link_bw, link_act, now, *,
                 backend: str = "auto"
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray, float]:
    """Run one fused flush pass over all transfer slots.

    See :func:`.ref.event_engine_ref` for the argument contract. Returns
    a host ``(rem_now, rate_new, eta_new, eta_min)`` tuple regardless of
    backend.

    The kernel routes see times relative to the flush instant: ``eta -
    now`` is taken and ``now`` added back on the host in float64, so the
    float32 chip resolves the gap to each completion instead of the
    absolute clock (at a 1e6 s clock float32 steps by 0.06 s). In float64
    the shift is exact — ``(eta - now) - 0`` is ``eta - now`` and
    rounding is monotone, so ``now + min(x) == min(now + x)`` — and the
    interpret route stays bit-identical to the oracle.
    """
    if backend != "numpy":
        import jax  # deferred: the oracle route needs no jax

        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "numpy"
    if backend in ("pallas", "interpret"):
        from .kernel import event_engine_kernel
        interpret = backend == "interpret"
        dtype = np.float64 if interpret else np.float32
        eta_rel = np.asarray(eta, np.float64) - now
        with jax.enable_x64(interpret):
            out = event_engine_kernel(
                np.asarray(path, np.int32), np.asarray(rem, dtype),
                np.asarray(rate, dtype), eta_rel.astype(dtype),
                np.asarray(link_bw, dtype), np.asarray(link_act, dtype),
                dtype(0.0), interpret=interpret)
        rem_now, rate_new, eta_new, eta_min = out
        return (np.asarray(rem_now, np.float64),
                np.asarray(rate_new, np.float64),
                now + np.asarray(eta_new, np.float64), now + float(eta_min))
    if backend != "numpy":
        raise ValueError(f"unknown event_engine backend {backend!r} "
                         "(want 'auto'|'pallas'|'interpret'|'numpy')")
    return event_engine_ref(path, rem, rate, eta, link_bw, link_act, now)
