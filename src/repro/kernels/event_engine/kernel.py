"""Pallas TPU kernel for the batched event-engine flush.

The ``net="device"`` engine backend defers every link-occupancy change
within one event instant and then runs this single fused pass: remaining
bytes are reconstructed from the cached ``(rate, eta)`` pair, every slot
is re-rated (gather-min of per-link fair shares along its path, as in
:mod:`repro.kernels.net_rerate`), and a running-min reduction over the new
etas yields the next NET wake-up — one device call per drained instant
instead of one per event.

Layout matches ``net_rerate``: the jitted wrapper computes the per-link
fair shares, prepends an ``inf`` sentinel for the path matrix's ``-1``
padding and gathers them into a ``(max_links, slots)`` share plane (a
1-D gather inside the kernel does not lower on TPU); the slot axis rides
the lanes (padded to a lane multiple) and the small level axis the
sublanes. The kernel takes the min over levels, reconstructs remaining
bytes, recomputes every eta and reduces to the earliest one. The
slot-state rows (rem/rate/eta) are ``(1, slots)`` VMEM rows, ``now`` sits
in SMEM. One program sees the whole batch — even 100k slots is a few MB
of VMEM.

A flush crosses between host and device once each way. The engine's
route (:mod:`.ops`) builds both inputs of :func:`_flush_call` in numpy
(:func:`host_inputs`: the transposed, padded int32 path and one flat
float buffer of everything else), moves them with one
``jax.device_put``, runs the one program — unpack, share gather,
kernel, pack — and copies its single ``(4, slots)`` output back, which
:func:`host_outputs` cuts to the real slots and widens in numpy.
:func:`event_engine_kernel` is the same program for a traced caller
(the jaxpr audit, the TPU compile test), with the layout built in jax
(:func:`kernel_inputs`) and the outputs sliced inside the trace.

Times may be absolute or relative to the flush instant: the ops wrapper
passes ``eta - now`` and ``now = 0`` so that float32 on the chip resolves
the gap to the next completion, not the absolute clock.

Interpret mode under ``jax.enable_x64`` computes in float64 and is
bit-identical to ``ref.event_engine_ref`` (where/multiply/divide/min are
exact IEEE ops) — the contract the jaxpr auditor and
``tests/test_kernels.py`` pin.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lane width of the slot axis; the level axis is padded to the float32
# sublane minimum so the compiled layout is legal on TPU.
_LANES = 128
_SUBLANES = 8


def _event_flush_kernel(share_ref, rem_ref, rate_ref, eta_ref, now_ref,
                        rem_out, rate_out, eta_out, eta_min_ref):
    # min fair share over each slot's path; all-padding columns reduce to
    # the bare inf sentinel: dead, rate 0
    rate_new = jnp.min(share_ref[...], axis=0, keepdims=True)   # (1, slots)
    rate_new = jnp.where(rate_new < jnp.inf, rate_new, 0.0)
    now = now_ref[0, 0]
    rate_old = rate_ref[...]
    carried = rate_old > 0.0
    # mask dead slots' inf etas before the multiply (no 0*inf NaNs)
    eta_c = jnp.where(carried, eta_ref[...], 0.0)
    rem_now = jnp.maximum(
        jnp.where(carried, rate_old * (eta_c - now), rem_ref[...]), 0.0)
    live = rate_new > 0.0
    eta_new = jnp.where(live, now + rem_now / jnp.where(live, rate_new, 1.0),
                        jnp.inf)
    rem_out[...] = rem_now
    rate_out[...] = rate_new
    eta_out[...] = eta_new
    eta_min_ref[0, 0] = jnp.min(eta_new)


def _padded(slots: int, levels: int) -> tuple[int, int]:
    """The kernel's slot and level extents: at least one lane group of
    slots (an empty flush runs the same program), levels to the sublane
    multiple."""
    return (max(1, -(-slots // _LANES)) * _LANES,
            -(-levels // _SUBLANES) * _SUBLANES)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _flush_call(path, floats, *, interpret: bool):
    """The whole flush as one program: unpack the staged inputs, gather
    the shares, run the kernel, and pack its outputs into one
    ``(4, slots)`` array — rem, rate and eta rows, then ``eta_min``
    along the fourth."""
    slots = path.shape[1]
    links = (floats.shape[0] - 3 * slots - 1) // 2
    dtype = floats.dtype
    rem, rate, eta = (floats[i * slots:(i + 1) * slots] for i in range(3))
    link_bw = floats[3 * slots:3 * slots + links]
    link_act = floats[3 * slots + links:-1]
    now = floats[-1]
    # per-link shares behind an inf cell at index 0, where the -1 padding
    # lands once every id is shifted by one; a flat gather keeps every
    # intermediate 2-D
    share = jnp.concatenate([jnp.full((1,), jnp.inf, dtype),
                             link_bw / jnp.maximum(1.0, link_act)])
    shares = jnp.take(share, (path + 1).reshape(-1),
                      mode="clip").reshape(path.shape)
    row = jax.ShapeDtypeStruct((1, slots), dtype)
    rem_now, rate_new, eta_new, eta_min = pl.pallas_call(
        _event_flush_kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 4
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[row, row, row, jax.ShapeDtypeStruct((1, 1), dtype)],
        interpret=interpret,
    )(shares, rem.reshape(1, slots), rate.reshape(1, slots),
      eta.reshape(1, slots), now.reshape(1, 1))
    return jnp.concatenate([rem_now, rate_new, eta_new,
                            jnp.broadcast_to(eta_min, (1, slots))])


def host_inputs(path, rem, rate, eta, link_bw, link_act, dtype):
    """The two inputs of :func:`_flush_call`, built in numpy on the host
    so that one transfer moves them: the ``(levels, slots)`` int32 path
    (transposed so slots ride the lanes, ``-1`` padded) and one flat
    ``dtype`` buffer ``[rem | rate | eta | link_bw | link_act | now]``
    whose slot rows are zero padded. Padded slots re-rate to 0 and an
    inf eta, dropping out of the min. ``now`` is 0: ``eta`` comes in
    relative to the flush instant."""
    slots, levels = path.shape
    s_pad, l_pad = _padded(slots, levels)
    path_t = np.full((l_pad, s_pad), -1, np.int32)
    path_t[:levels, :slots] = path.T
    links = len(link_bw)
    floats = np.zeros(3 * s_pad + 2 * links + 1, dtype)
    rows = floats[:3 * s_pad].reshape(3, s_pad)
    rows[0, :slots] = rem
    rows[1, :slots] = rate
    rows[2, :slots] = eta
    floats[3 * s_pad:3 * s_pad + links] = link_bw
    floats[3 * s_pad + links:-1] = link_act
    return path_t, floats


def host_outputs(packed: np.ndarray, slots: int):
    """:func:`_flush_call`'s packed output, copied to the host, as the
    float64 ``(rem_now, rate_new, eta_new, eta_min)`` of the ``slots``
    real slots."""
    rem_now, rate_new, eta_new = packed[:3, :slots].astype(np.float64)
    return rem_now, rate_new, eta_new, float(packed[3, 0])


def kernel_inputs(path, rem, rate, eta, link_bw, link_act, now):
    """:func:`host_inputs` in jax, for a traced caller: the same layout,
    dtypes following ``rem``."""
    dtype = jnp.asarray(rem).dtype
    slots, levels = path.shape
    s_pad, l_pad = _padded(slots, levels)
    path_t = jnp.pad(jnp.asarray(path, jnp.int32).T,
                     ((0, l_pad - levels), (0, s_pad - slots)),
                     constant_values=-1)
    rows = [jnp.pad(jnp.asarray(x, dtype), (0, s_pad - slots))
            for x in (rem, rate, eta)]
    floats = jnp.concatenate(rows + [
        jnp.asarray(link_bw, dtype), jnp.asarray(link_act, dtype),
        jnp.reshape(jnp.asarray(now, dtype), (1,))])
    return path_t, floats


def event_engine_kernel(path, rem, rate, eta, link_bw, link_act, now, *,
                        interpret: bool = False):
    """Same contract as :func:`..ref.event_engine_ref`, computed by the
    Pallas kernel. ``path`` is ``(slots, max_links)`` (-1 padded); dtypes
    follow ``rem`` (float32 compiled on TPU, float64 under x64 interpret).
    """
    slots = path.shape[0]
    packed = _flush_call(*kernel_inputs(path, rem, rate, eta, link_bw,
                                        link_act, now), interpret=interpret)
    return (packed[0, :slots], packed[1, :slots], packed[2, :slots],
            packed[3, 0])
