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

from .ref import event_engine_core, event_engine_ref

#: the flush's stages, timed as parts of the engine's ``net.flush`` phase
#: when a probe is handed in (``repro.obs``)
STAGE, LAUNCH, FETCH, APPLY = ("net.flush.stage", "net.flush.launch",
                               "net.flush.fetch", "net.flush.apply")


def event_engine(path, rem, rate, eta, link_bw, link_act, now, *,
                 backend: str = "auto", probe=None
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray, float]:
    """Run one fused flush pass over all transfer slots.

    See :func:`.ref.event_engine_ref` for the argument contract. Returns
    a host ``(rem_now, rate_new, eta_new, eta_min)`` tuple regardless of
    backend.

    Every route sees times relative to the flush instant: ``eta - now``
    is taken and ``now`` added back on the host in float64, so the
    float32 chip resolves the gap to each completion instead of the
    absolute clock (at a 1e6 s clock float32 steps by 0.06 s). In float64
    the shift is exact — ``(eta - now) - 0`` is ``eta - now`` and
    rounding is monotone, so ``now + min(x) == min(now + x)`` — and the
    interpret and numpy routes stay bit-identical to the oracle.

    With a ``probe`` the pass is timed in four parts: ``STAGE`` (host
    arrays to the kernel's device inputs: casts, the shift, transfers,
    pads, transpose), ``LAUNCH`` (the dispatch, outputs still on the
    device), ``FETCH`` (the wait and the copy back) and ``APPLY`` (the
    float64 add-back of ``now``). On the numpy route the oracle is the
    launch and the fetch copies nothing.
    """
    if backend != "numpy":
        import jax  # deferred: the oracle route needs no jax

        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "numpy"
    if backend not in ("pallas", "interpret", "numpy"):
        raise ValueError(f"unknown event_engine backend {backend!r} "
                         "(want 'auto'|'pallas'|'interpret'|'numpy')")
    args = (path, rem, rate, eta, link_bw, link_act, now)
    if probe is None:
        return _add_now(_fetch(_launch(_stage(*args, backend), backend)),
                        now)
    with probe.part(STAGE):
        staged = _stage(*args, backend)
    with probe.part(LAUNCH):
        out = _launch(staged, backend)
    with probe.part(FETCH):
        out = _fetch(out)
    with probe.part(APPLY):
        return _add_now(out, now)


def _stage(path, rem, rate, eta, link_bw, link_act, now, backend):
    dtype = np.float32 if backend == "pallas" else np.float64
    args = (np.asarray(path, np.int32), np.asarray(rem, dtype),
            np.asarray(rate, dtype),
            (np.asarray(eta, np.float64) - now).astype(dtype),
            np.asarray(link_bw, dtype), np.asarray(link_act, dtype))
    if backend == "numpy":
        return args
    import jax

    from .kernel import kernel_inputs
    with jax.enable_x64(backend == "interpret"):
        return kernel_inputs(*args, dtype(0.0))


def _launch(staged, backend):
    if backend == "numpy":
        return event_engine_core(*staged, 0.0)
    import jax

    from .kernel import kernel_launch
    interpret = backend == "interpret"
    with jax.enable_x64(interpret):
        return kernel_launch(*staged, interpret=interpret)


def _fetch(out):
    rem_now, rate_new, eta_new, eta_min = out
    return (np.asarray(rem_now, np.float64), np.asarray(rate_new, np.float64),
            np.asarray(eta_new, np.float64), float(eta_min))


def _add_now(out, now):
    rem_now, rate_new, eta_new, eta_min = out
    return rem_now, rate_new, now + eta_new, now + eta_min
