"""Pallas TPU kernel for the batched replica-strategy plan pass.

One arrival burst means up to ``jobs x missing-files`` fetch decisions;
the sequential strategies make each one with a Python loop over holders
(``point_bandwidth`` per candidate — millions of calls per run at the
500-site scale point). This kernel scores the whole burst in one fused
pass: a single ``fori_loop`` over the site axis carries five ``(1,
pairs)`` running buffers in VMEM — best effective bandwidth and its
(first-occurrence) argmax for the global and the region-local candidate
sets, plus the local flag of the winning global row — and the store
verdict is one vectorized compare. Peak memory is O(sites x pairs);
the dense per-decision alternative would be a ``(pairs, sites, files)``
materialization, which is exactly what the jaxpr auditor's rank/budget
caps ban.

Layout: the pair axis rides the lanes (padded to 128) everywhere; the
site axis rides the sublanes of the ``(sites, pairs)`` inputs (padded to
8) and is walked by the loop. ``serve`` sits in SMEM (scalar read per
iteration, the ``now`` idiom of ``event_engine``). Padded site rows are
unfetchable (mask 0 -> key -1) and never win; padded pair columns are
garbage but sliced off.

Bit-identity: the running maximum updates on strict ``>`` only, so ties
keep the earliest site — exactly ``np.argmax``'s first occurrence — and
where/divide/compare are exact IEEE ops, so under
``jax.enable_x64`` interpret mode the kernel reproduces
``ref.strategy_plan_ref`` bit for bit (pinned by
``tests/test_kernels.py``). Compiled TPU execution is float32, the
tolerance tier.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8


def _strategy_plan_kernel(bw_ref, fetch_ref, local_ref, free_ref, size_ref,
                          serve_ref, srcg_ref, srcl_ref, hasl_ref,
                          interg_ref, store_ref):
    n_sites, n_pairs = bw_ref.shape        # (S, P)
    dtype = bw_ref.dtype

    def site_body(h, carry):
        best_g, src_g, loc_g, best_l, src_l = carry    # each (1, P)
        bw_row = bw_ref[pl.ds(h, 1), :]
        f_row = fetch_ref[pl.ds(h, 1), :] > 0.0
        l_row = local_ref[pl.ds(h, 1), :] > 0.0
        eff = bw_row / (1.0 + serve_ref[0, h])
        key_g = jnp.where(f_row, eff, -1.0)
        key_l = jnp.where(f_row & l_row, eff, -1.0)
        hf = h.astype(dtype)
        upd_g = key_g > best_g             # strict: ties keep first site
        src_g = jnp.where(upd_g, hf, src_g)
        loc_g = jnp.where(upd_g, jnp.where(l_row, 1.0, 0.0), loc_g)
        best_g = jnp.where(upd_g, key_g, best_g)
        upd_l = key_l > best_l
        src_l = jnp.where(upd_l, hf, src_l)
        best_l = jnp.where(upd_l, key_l, best_l)
        return best_g, src_g, loc_g, best_l, src_l

    # init below the -1 mask value: the first site always updates, so the
    # carried argmax is always a real row index
    neg = jnp.full((1, n_pairs), -2.0, dtype)
    zero = jnp.zeros((1, n_pairs), dtype)
    best_g, src_g, loc_g, best_l, src_l = jax.lax.fori_loop(
        0, n_sites, site_body, (neg, zero, zero, neg, zero))
    srcg_ref[...] = src_g
    srcl_ref[...] = src_l
    # a real local candidate scored >= 0 (bandwidth is nonnegative); the
    # all-masked column never rose above -1
    hasl_ref[...] = jnp.where(best_l >= 0.0, 1.0, 0.0)
    interg_ref[...] = 1.0 - loc_g
    store_ref[...] = jnp.where(free_ref[...] >= size_ref[...], 1.0, 0.0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _strategy_plan_call(bw, fetch, local, free, size, serve, *,
                        interpret: bool):
    n_pairs = bw.shape[1]
    dtype = bw.dtype
    row = jax.ShapeDtypeStruct((1, n_pairs), dtype)
    return pl.pallas_call(
        _strategy_plan_kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 5
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 5,
        out_shape=[row] * 5,
        interpret=interpret,
    )(bw, fetch, local, free, size, serve)


def strategy_plan_kernel(bw, fetch, local, serve, free, size, *,
                         interpret: bool = False):
    """Same contract as :func:`..ref.strategy_plan_ref`, computed by the
    Pallas kernel. Dtypes follow ``bw`` (float32 compiled on TPU, float64
    under x64 interpret)."""
    bw = jnp.asarray(bw)
    dtype = bw.dtype
    n_sites, n_pairs = bw.shape
    if n_pairs == 0 or n_sites == 0:
        z = jnp.zeros((n_pairs,), dtype)
        return z, z, z, z, z
    pad_s = (-n_sites) % _SUBLANES
    pad_p = (-n_pairs) % _LANES
    bw_p = jnp.pad(bw, ((0, pad_s), (0, pad_p)))
    fetch_p = jnp.pad(jnp.asarray(fetch, dtype), ((0, pad_s), (0, pad_p)))
    local_p = jnp.pad(jnp.asarray(local, dtype), ((0, pad_s), (0, pad_p)))
    free_p = jnp.pad(jnp.asarray(free, dtype), (0, pad_p)).reshape(1, -1)
    # padded pairs get size=1 > free=0 (store 0); all columns sliced off
    size_p = jnp.pad(jnp.asarray(size, dtype), (0, pad_p),
                     constant_values=1.0).reshape(1, -1)
    serve_p = jnp.pad(jnp.asarray(serve, dtype), (0, pad_s)).reshape(1, -1)
    out = _strategy_plan_call(bw_p, fetch_p, local_p, free_p, size_p,
                              serve_p, interpret=interpret)
    return tuple(o[0, :n_pairs] for o in out)
