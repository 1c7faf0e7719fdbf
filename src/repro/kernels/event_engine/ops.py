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

    With a ``probe`` the pass is timed in four parts: ``STAGE`` (the
    kernel's inputs built on the host in numpy — casts, the shift, the
    path's transpose, the pads — and moved by one ``jax.device_put``),
    ``LAUNCH`` (the one program's dispatch, its output still on the
    device), ``FETCH`` (the wait, the one copy back, and the cut to the
    real slots widened to float64 in numpy) and ``APPLY`` (the float64
    add-back of ``now``). On the numpy route the oracle is the launch and
    the fetch copies nothing.
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
        return _add_now(_fetch(_launch(_stage(*args, backend), backend),
                               backend), now)
    with probe.part(STAGE):
        staged = _stage(*args, backend)
    with probe.part(LAUNCH):
        out = _launch(staged, backend)
    with probe.part(FETCH):
        out = _fetch(out, backend)
    with probe.part(APPLY):
        return _add_now(out, now)


def _stage(path, rem, rate, eta, link_bw, link_act, now, backend):
    if backend == "numpy":
        return (np.asarray(path, np.int32), np.asarray(rem, np.float64),
                np.asarray(rate, np.float64),
                np.asarray(eta, np.float64) - now,
                np.asarray(link_bw, np.float64),
                np.asarray(link_act, np.float64))
    import jax

    from .kernel import host_inputs
    dtype = np.float32 if backend == "pallas" else np.float64
    staged = host_inputs(np.asarray(path), rem, rate,
                         np.asarray(eta, np.float64) - now, link_bw,
                         link_act, dtype)
    with jax.enable_x64(backend == "interpret"):
        return jax.device_put(staged), len(path)


def _launch(staged, backend):
    if backend == "numpy":
        return event_engine_core(*staged, 0.0)
    import jax

    from .kernel import _flush_call
    (path, floats), slots = staged
    interpret = backend == "interpret"
    with jax.enable_x64(interpret):
        return _flush_call(path, floats, interpret=interpret), slots


def _fetch(out, backend):
    if backend == "numpy":
        return out
    from .kernel import host_outputs
    packed, slots = out
    return host_outputs(np.asarray(packed), slots)


def _add_now(out, now):
    rem_now, rate_new, eta_new, eta_min = out
    return rem_now, rate_new, now + eta_new, now + eta_min
