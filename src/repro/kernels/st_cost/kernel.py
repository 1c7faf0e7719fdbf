"""Pallas TPU kernel for the blocked shortest-transfer cost pass.

The jitted ``shortesttransfer`` broker costs every (job, site) pair of a
dispatch batch each time a burst arrives. The pre-blocked formulation
reduced over holders by broadcasting a ``(sites, files, sites)`` tensor —
~200 MB at the 500-site scale point — so, exactly like ``value_score``,
this kernel runs a ``fori_loop`` over the holder axis carrying a
``(files, sites)`` running max in VMEM, then a second ``fori_loop`` over
the file axis accumulating the per-job staging times into a ``(jobs,
sites)`` buffer: two VPU-shaped fused passes, no MXU, peak memory
O(sites x files + jobs x sites).

Layout: the destination-site axis rides the lanes (padded to 128)
everywhere; the file axis rides the sublanes of the ``(files, sites)``
buffers and the lanes of ``fetch_mask``/``sizes`` (padded to 128 so both
orientations agree); jobs ride the lanes of the transposed requirement
matrix and the sublanes of the output (padded to 128). Padding rows/cols
are all zero: they never win the holder max, padded files are never
required (their terms are exact zeros), and padded destination columns
cost ``inf`` but are sliced off.

Bit-identity: the holder max is order-independent and max/divide are
exact IEEE ops; the file sum runs sequentially over ascending file index
— the same order numpy reduces the major axis of a 2-D array — and a
zero term leaves a nonnegative running sum unchanged, so under
``jax.enable_x64`` interpret mode the kernel reproduces
``ref.st_cost_ref`` bit for bit (pinned by ``tests/test_kernels.py``).
Compiled TPU execution is float32 (no f64 on TPU), so on TPU the route
is approximate at the ~1e-7 relative level, like the other kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def _st_cost_kernel(bw_ref, fetch_ref, presence_t_ref, req_t_ref, sizes_ref,
                    rel_ref, online_ref, out_ref, t_fs_ref):
    # bw (S_h, S) [holder, dst]; fetch (S_h, F) 0/1 fetchable holders;
    # presence_t (F, S) 0/1 all holders; req_t (F, J) 0/1 requirement
    # masks; t_fs (F, S) scratch for the per-(file, dst) staging times
    n_f, n_s = presence_t_ref.shape
    n_j = req_t_ref.shape[1]
    dtype = bw_ref.dtype

    # Both loops run over the *padded* axes: padded holder rows hold no
    # files (zero contrib to the max) and padded files are required by no
    # job (exact-zero terms of the sum), so results are bit-identical to
    # looping over the true counts — and compilation buckets by padded
    # shape (multiples of 128) instead of retracing per batch-union size.

    # pass 1 — best fetchable bandwidth per (file, dst): running max over
    # holder rows. Each holder's fetch row is stood up as a column over
    # files (the row -> column reshape value_score uses).
    def holder_body(h, best):
        pcol = fetch_ref[pl.ds(h, 1), :].reshape(-1, 1)          # (F, 1)
        brow = bw_ref[pl.ds(h, 1), :]                            # (1, S)
        contrib = jnp.where(pcol > 0.0, brow, 0.0)
        return jnp.maximum(best, contrib)

    best = jax.lax.fori_loop(0, fetch_ref.shape[0], holder_body,
                             jnp.zeros((n_f, n_s), dtype))
    sizes_col = sizes_ref[...].reshape(-1, 1)                    # (F, 1)
    t_fs_ref[...] = jnp.where(best > 0.0, sizes_col / best, jnp.inf)

    # pass 2 — per-job staging time: sequential sum over ascending file
    # index of the missing files' transfer estimates.
    def file_body(f, acc):
        req_col = req_t_ref[pl.ds(f, 1), :].reshape(-1, 1)       # (J, 1)
        pres_row = presence_t_ref[pl.ds(f, 1), :]                # (1, S)
        t_row = t_fs_ref[pl.ds(f, 1), :]                         # (1, S)
        miss = (req_col > 0.0) & (pres_row <= 0.0)               # (J, S)
        return acc + jnp.where(miss, t_row, 0.0)

    t = jax.lax.fori_loop(0, n_f, file_body,
                          jnp.zeros((n_j, n_s), dtype))
    cost = jnp.maximum(t, rel_ref[...])                          # (1, S) bc
    out_ref[...] = jnp.where(online_ref[...] > 0.0, cost, jnp.inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _st_cost_call(bw, fetch, presence_t, req_t, sizes, rel, online, *,
                  interpret: bool):
    out_shape = (req_t.shape[1], bw.shape[1])
    return pl.pallas_call(
        _st_cost_kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 7,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(out_shape, bw.dtype),
        scratch_shapes=[pltpu.VMEM(presence_t.shape, bw.dtype)],
        interpret=interpret,
    )(bw, fetch, presence_t, req_t, sizes, rel, online)


def st_cost_kernel(bw, fetch_mask, presence, sizes, required, rel, online,
                   *, interpret: bool = False):
    """Same contract as :func:`..ref.st_cost_ref`, computed by the Pallas
    kernel. Dtypes follow ``bw`` (float32 compiled on TPU, float64 under
    x64 interpret)."""
    bw = jnp.asarray(bw)
    dtype = bw.dtype
    n_sites, n_files = jnp.asarray(presence).shape
    n_jobs = jnp.asarray(required).shape[0]
    if n_jobs == 0 or n_sites == 0:
        return jnp.zeros((n_jobs, n_sites), dtype)
    if n_files == 0:
        # nothing to stage: queue time only (the oracle's max(0, rel)
        # masked to online sites), no pallas_call over a 0-wide file axis
        cost = jnp.maximum(jnp.zeros((n_jobs, n_sites), dtype),
                           jnp.asarray(rel, dtype)[None, :])
        return jnp.where(jnp.asarray(online, dtype)[None, :] > 0.0, cost,
                         jnp.inf)
    pad_s8 = (-n_sites) % 8              # holder rows (sublanes)
    pad_s = (-n_sites) % _LANES          # dst columns (lanes)
    pad_f = (-n_files) % _LANES          # files: lanes of fetch/sizes and
    pad_j = (-n_jobs) % _LANES           #   sublanes of the (F, S) buffers
    bw_p = jnp.pad(jnp.asarray(bw, dtype), ((0, pad_s8), (0, pad_s)))
    fetch_p = jnp.pad(jnp.asarray(fetch_mask, dtype),
                      ((0, pad_s8), (0, pad_f)))
    presence_t_p = jnp.pad(jnp.asarray(presence, dtype).T,
                           ((0, pad_f), (0, pad_s)))
    req_t_p = jnp.pad(jnp.asarray(required, dtype).T,
                      ((0, pad_f), (0, pad_j)))
    sizes_p = jnp.pad(jnp.asarray(sizes, dtype), (0, pad_f)).reshape(1, -1)
    rel_p = jnp.pad(jnp.asarray(rel, dtype), (0, pad_s)).reshape(1, -1)
    online_p = jnp.pad(jnp.asarray(online, dtype), (0, pad_s)).reshape(1, -1)
    out = _st_cost_call(bw_p, fetch_p, presence_t_p, req_t_p, sizes_p,
                        rel_p, online_p, interpret=interpret)
    return out[:n_jobs, :n_sites]
