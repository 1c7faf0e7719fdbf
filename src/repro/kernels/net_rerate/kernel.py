"""Pallas TPU kernel for the fluid-network re-rate + next-completion scan.

The DES re-rates transfers whenever link occupancy changes: every active
transfer's rate is ``min over its crossed links of bandwidth / max(1,
active)`` and the engine needs the earliest ``now + remaining / rate`` to
schedule the next NET wake-up. At 100k concurrent transfers that is a
(slots x path) gather-min plus a masked min-reduction — one VPU-shaped
pass, no MXU.

Layout: the jitted wrapper computes the per-link shares, prepends an
``inf`` sentinel for the path matrix's ``-1`` padding and gathers them
into a ``(max_links, slots)`` share plane (a 1-D gather inside the kernel
does not lower on TPU), so the slot axis lands on lanes, padded to a lane
multiple, and the small link-level axis on sublanes. The kernel takes
the min over levels and scans for the next completion. A single program
sees the whole batch: even at 100k slots the operands are ~2 MB, well
under VMEM.

Interpret mode runs the same kernel eagerly with jnp on CPU; under
``jax.enable_x64`` it computes in float64 and is then bit-identical to
``ref.net_rerate_ref`` (divide/min are exact IEEE ops) — that is the
contract ``tests/test_kernels.py`` pins.
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


def _rerate_scan_kernel(share_ref, rem_ref, now_ref, rate_ref, eta_ref):
    # min fair share over each slot's path; all-padding columns reduce to
    # the bare inf sentinel and get rate 0
    rate = jnp.min(share_ref[...], axis=0, keepdims=True)       # (1, slots)
    rate = jnp.where(rate < jnp.inf, rate, 0.0)
    rate_ref[...] = rate
    now = now_ref[0, 0]
    # live slots only: padding rows have rate 0 and drop out of the min
    eta = jnp.where(rate > 0.0, now + rem_ref[...] / rate, jnp.inf)
    eta_ref[0, 0] = jnp.min(eta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _rerate_call(path, rem, link_bw, link_act, now, *, interpret: bool):
    slots = path.shape[1]
    dtype = rem.dtype
    # per-link shares behind an inf cell at index 0, where the -1 padding
    # lands once every id is shifted by one; a flat gather keeps every
    # intermediate 2-D
    share = jnp.concatenate([jnp.full((1,), jnp.inf, dtype),
                             link_bw / jnp.maximum(1.0, link_act)])
    shares = jnp.take(share, (path + 1).reshape(-1),
                      mode="clip").reshape(path.shape)
    rate, eta = pl.pallas_call(
        _rerate_scan_kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 2
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_shape=[jax.ShapeDtypeStruct((1, slots), dtype),
                   jax.ShapeDtypeStruct((1, 1), dtype)],
        interpret=interpret,
    )(shares, rem.reshape(1, slots), now.reshape(1, 1))
    return rate[0], eta[0, 0]


def net_rerate_kernel(path, rem, link_bw, link_act, now, *,
                      interpret: bool = False):
    """Same contract as :func:`..ref.net_rerate_ref`, computed by the
    Pallas kernel. ``path`` is ``(slots, max_links)`` (-1 padded); dtypes
    follow ``rem`` (float32 compiled on TPU, float64 under x64 interpret).
    """
    path = jnp.asarray(path, jnp.int32)
    rem = jnp.asarray(rem)
    slots, levels = path.shape
    if slots == 0:
        return jnp.zeros((0,), rem.dtype), jnp.asarray(jnp.inf, rem.dtype)
    pad_s = (-slots) % _LANES
    pad_l = (-levels) % _SUBLANES
    # transpose so slots ride the lanes; padding rows/slots are all -1 and
    # come out with rate 0, which the eta scan ignores
    path_t = jnp.pad(path.T, ((0, pad_l), (0, pad_s)), constant_values=-1)
    rem_p = jnp.pad(rem, (0, pad_s))
    rate, eta = _rerate_call(path_t, rem_p, jnp.asarray(link_bw, rem.dtype),
                             jnp.asarray(link_act, rem.dtype),
                             jnp.asarray(now, rem.dtype),
                             interpret=interpret)
    return rate[:slots], eta
