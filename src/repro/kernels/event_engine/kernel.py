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


@functools.partial(jax.jit, static_argnames=("interpret",))
def _flush_call(path, rem, rate, eta, link_bw, link_act, now, *,
                interpret: bool):
    slots = path.shape[1]
    dtype = rem.dtype
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
    return rem_now[0], rate_new[0], eta_new[0], eta_min[0, 0]


def kernel_inputs(path, rem, rate, eta, link_bw, link_act, now):
    """The device inputs of :func:`_flush_call` and the slot count: the
    host arrays transferred, the path transposed so slots ride the lanes,
    and the slot axis padded to a lane multiple. Padded slots are all -1
    path columns with zeroed state — they re-rate to 0 and an inf eta,
    dropping out of the min. Dtypes follow ``rem``."""
    path = jnp.asarray(path, jnp.int32)
    rem = jnp.asarray(rem)
    slots, levels = path.shape
    pad_s = (-slots) % _LANES
    pad_l = (-levels) % _SUBLANES
    path_t = jnp.pad(path.T, ((0, pad_l), (0, pad_s)), constant_values=-1)
    rem_p = jnp.pad(rem, (0, pad_s))
    rate_p = jnp.pad(jnp.asarray(rate, rem.dtype), (0, pad_s))
    eta_p = jnp.pad(jnp.asarray(eta, rem.dtype), (0, pad_s))
    return (path_t, rem_p, rate_p, eta_p, jnp.asarray(link_bw, rem.dtype),
            jnp.asarray(link_act, rem.dtype),
            jnp.asarray(now, rem.dtype)), slots


def kernel_launch(inputs, slots: int, *, interpret: bool = False):
    """Dispatch the flush on :func:`kernel_inputs`' output; the results
    stay on the device, cut back to ``slots``."""
    if slots == 0:
        z = jnp.zeros((0,), inputs[1].dtype)
        return z, z, z, jnp.asarray(jnp.inf, inputs[1].dtype)
    rem_now, rate_new, eta_new, eta_min = _flush_call(*inputs,
                                                      interpret=interpret)
    return rem_now[:slots], rate_new[:slots], eta_new[:slots], eta_min


def event_engine_kernel(path, rem, rate, eta, link_bw, link_act, now, *,
                        interpret: bool = False):
    """Same contract as :func:`..ref.event_engine_ref`, computed by the
    Pallas kernel. ``path`` is ``(slots, max_links)`` (-1 padded); dtypes
    follow ``rem`` (float32 compiled on TPU, float64 under x64 interpret).
    """
    inputs, slots = kernel_inputs(path, rem, rate, eta, link_bw, link_act,
                                  now)
    return kernel_launch(inputs, slots, interpret=interpret)
