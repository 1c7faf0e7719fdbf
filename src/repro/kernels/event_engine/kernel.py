"""Pallas TPU kernel for the batched event-engine flush.

The ``net="device"`` engine backend defers every link-occupancy change
within one event instant and then runs one fused pass: every slot is
re-rated (min of per-link fair shares along its path, as the
incremental ``net="numpy"`` route computes it), remaining bytes are
reconstructed from the cached ``(rate, eta)`` pair, and a running-min
over the new etas yields the next NET wake-up — one device call per
drained instant instead of one per event.

The device picks each slot's least share by rank, and the host does the
arithmetic in float64. Fair shares (``bandwidth / max(1, active)``) are
computed on the host in float64 and ranked there
(:func:`share_ranks`: each link's rank is the number of shares strictly
below its own, so equal shares keep one rank and the order is the
float64 order). The chip gathers the ranks along every path and takes
the min over levels, an integer program with nothing to round; the host
maps each least rank back to its float64 share and reconstructs
``rem``/``eta`` in float64 (``ref.settle``). So a flush on the chip is
bit-identical to the float64 oracle, and two completions or two shares
that float64 keeps tied stay tied. (A float32 flush split them: the
chip's float32 division is off by up to 2 ulps, and float32 rounding
alone splits ties of a long run.)

Layout: the ranks carry a sentinel at index 0, past every real rank,
where the path matrix's ``-1`` padding lands once every id is shifted
by one; the jitted wrapper gathers them into a ``(max_links, slots)``
plane (a 1-D gather inside the kernel does not lower on TPU); slots
ride the lanes (padded to a lane multiple) and the small level axis
the sublanes. One program sees the whole batch.

A flush crosses between host and device once each way. The engine's
route (:mod:`.ops`) builds both inputs of :func:`_flush_call` in numpy
(:func:`host_inputs`: the transposed, padded int32 path and the int32
ranks) and hands them to the one program — gather, kernel — whose
dispatch moves them to the device (no ``jax.device_put``); it copies
the ``(1, slots)`` int32 output back, which :func:`host_rates` turns
into float64 rates of the real slots. :func:`_flush_call` is also
what the jaxpr audit traces and the TPU compile test compiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lane width of the slot axis; the level axis is padded to the 32-bit
# sublane minimum so the compiled layout is legal on TPU.
_LANES = 128
_SUBLANES = 8


def _least_rank_kernel(rank_ref, least_ref):
    # the least share rank over each slot's path; all-padding columns
    # reduce to the sentinel: dead, rate 0
    least_ref[...] = jnp.min(rank_ref[...], axis=0, keepdims=True)


def _padded(slots: int, levels: int) -> tuple[int, int]:
    """The kernel's slot and level extents: at least one lane group of
    slots (an empty flush runs the same program), levels to the sublane
    multiple."""
    return (max(1, -(-slots // _LANES)) * _LANES,
            -(-levels // _SUBLANES) * _SUBLANES)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _flush_call(path, ranks, *, interpret: bool):
    """The device's part of a flush as one program: gather each slot's
    link ranks (``ranks[0]`` is the padding's sentinel; ids are shifted
    by one, a flat gather keeps every intermediate 2-D) and take the min
    over levels, a ``(1, slots)`` int32 row."""
    slots = path.shape[1]
    plane = jnp.take(ranks, (path + 1).reshape(-1),
                     mode="clip").reshape(path.shape)
    return pl.pallas_call(
        _least_rank_kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, slots), jnp.int32),
        interpret=interpret,
    )(plane)


def share_ranks(link_bw, link_act):
    """The float64 fair share of every link, ranked on the host: the
    int32 ranks behind the sentinel (one past the last link) and the
    table that maps a rank back to its share (the sorted shares, then
    ``inf`` at the sentinel's rank)."""
    share = np.asarray(link_bw, np.float64) / np.maximum(
        1.0, np.asarray(link_act, np.float64))
    table = np.sort(share)
    ranks = np.empty(len(share) + 1, np.int32)
    ranks[0] = len(share)
    ranks[1:] = np.searchsorted(table, share, side="left")
    return ranks, np.append(table, np.inf)


def host_inputs(path, link_bw, link_act):
    """The two inputs of :func:`_flush_call`, built in numpy on the host
    and handed to its dispatch as they are: the ``(levels, slots)``
    int32 path (transposed so slots ride the lanes, ``-1`` padded) and
    the ranks of :func:`share_ranks`; and the table that
    :func:`host_rates` reads.
    Padded slots reduce to the sentinel and re-rate to 0."""
    slots, levels = path.shape
    s_pad, l_pad = _padded(slots, levels)
    path_t = np.full((l_pad, s_pad), -1, np.int32)
    path_t[:levels, :slots] = path.T
    ranks, table = share_ranks(link_bw, link_act)
    return (path_t, ranks), table


def host_rates(least: np.ndarray, slots: int, table: np.ndarray):
    """:func:`_flush_call`'s output, copied to the host, as the float64
    new rates of the ``slots`` real slots (``inf`` where a slot has no
    link, which ``ref.settle`` zeroes)."""
    return table[least[0, :slots]]
