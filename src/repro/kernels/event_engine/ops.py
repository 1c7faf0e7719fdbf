"""Public wrapper for the event-engine flush (pallas / interpret / numpy).

This op is called from the discrete-event loop (host code, once per
drained event instant), so the wrapper returns host numpy values and
picks the backend per call:

  * ``"auto"``   — the compiled Pallas kernel on TPU; the float64 numpy
    oracle on CPU (no per-instant jax dispatch overhead). This is what
    ``net="device"`` uses.
  * ``"pallas"`` — force the compiled kernel. The chip picks each
    slot's least fair share by its float64 rank, and the host does the
    arithmetic in float64, so the route is bit-identical to the oracle
    on any chip (:mod:`.kernel`).
  * ``"interpret"`` — the same program under the Pallas interpreter:
    slow, bit-identical to the oracle; used by the kernel tests and the
    ``net="device-interpret"`` engine flag.
  * ``"numpy"``  — the oracle directly.
"""

from __future__ import annotations

import numpy as np

from .ref import event_engine_core, event_engine_ref, settle

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
    is taken and ``now`` added back on the host in float64. The shift is
    exact — ``(eta - now) - 0`` is ``eta - now`` and rounding is
    monotone, so ``now + min(x) == min(now + x)`` — and every route
    stays bit-identical to the oracle.

    With a ``probe`` the pass is timed in four parts: ``STAGE`` (the
    fair shares ranked in float64 and the path transposed and padded,
    in numpy only), ``LAUNCH`` (the one program's dispatch, handed the
    numpy operands, so it carries their one transfer to the device; its
    output stays there), ``FETCH`` (the wait, the one copy back, and
    the float64 rates and settle of :func:`.ref.settle`) and ``APPLY``
    (the float64 add-back of ``now``). On the numpy route the oracle is
    the launch and the fetch copies nothing.

    The kernel routes make no ``jax.device_put`` and no eager jax op:
    the jitted program's own dispatch moves its numpy operands, which
    skips JAX's Python transfer layer. Every call passes numpy
    operands, so the program has one jit cache entry per shape and the
    warm-up that runs this op covers the window.
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
    rem, rate = np.asarray(rem, np.float64), np.asarray(rate, np.float64)
    eta = np.asarray(eta, np.float64) - now
    if backend == "numpy":
        return (np.asarray(path, np.int32), rem, rate, eta,
                np.asarray(link_bw, np.float64),
                np.asarray(link_act, np.float64))
    from .kernel import host_inputs
    inputs, table = host_inputs(np.asarray(path), link_bw, link_act)
    return inputs, (len(path), table, rem, rate, eta)


def _launch(staged, backend):
    if backend == "numpy":
        return event_engine_core(*staged, 0.0)
    from .kernel import _flush_call
    inputs, on_host = staged
    return (_flush_call(*inputs, interpret=backend == "interpret"),
            on_host)


def _fetch(out, backend):
    if backend == "numpy":
        return out
    from .kernel import host_rates
    least, (slots, table, rem, rate, eta) = out
    return settle(rem, rate, eta, host_rates(np.asarray(least), slots,
                                             table), 0.0)


def _add_now(out, now):
    rem_now, rate_new, eta_new, eta_min = out
    return rem_now, rate_new, now + eta_new, now + eta_min
