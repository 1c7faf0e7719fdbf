"""Pallas TPU kernel for the replica value-scoring pass.

The replication economy re-scores every (site, file) pair each time its
periodic DES event fires: ``bestbw[s, f] = max over holders h != s of
bw[h, s]`` followed by ``value = demand * size / bestbw`` (see ``ref.py``
for the exact contract). Naively that reduction materializes a
``(sites, files, sites)`` tensor — ~200 MB at the 500-site scale point —
so the kernel instead runs a ``fori_loop`` over the holder axis carrying
an ``(sites, block)`` running max in VMEM: one VPU-shaped fused pass, no
MXU. File columns are independent, so a grid walks the file axis in
blocks of at most ``_BLOCK_F`` lanes and VMEM use stays bounded at any
catalog size (a whole ``(500, 10 000)`` plane would not fit).

Layout: the file axis rides the lanes (padded to the block), the site
axis the sublanes (padded to 8). The bandwidth matrix is ``(sites,
sites)`` with the destination axis on lanes; each holder's row is stood
up as a column over destinations. Padding rows of ``presence`` are all
zero and padded ``bw`` entries are 0, so they never win the max; padded
file columns score 0 and are sliced off.

Interpret mode runs the same kernel eagerly on CPU; under
``jax.enable_x64`` it computes in float64 and is then
bit-identical to ``ref.value_score_ref`` (max/divide are exact IEEE ops;
the max-reduction is order-independent) — the contract pinned by
``tests/test_kernels.py`` and the ``econ="pallas-interpret"`` engine flag.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8
_BLOCK_F = 512          # file lanes per grid step


def _value_score_kernel(demand_ref, sizes_ref, presence_ref, bw_ref,
                        out_ref, *, plain: bool):
    demand = demand_ref[...]                       # (S, F)
    n_sites = demand.shape[0]
    # dst-site index per output row, used to mask self-supply (h == s)
    row_id = jax.lax.broadcasted_iota(jnp.int32, demand.shape, 0)

    def body(h, best):
        prow = presence_ref[pl.ds(h, 1), :]                     # (1, F)
        # holder h's bw row, stood up as a column over destinations; its
        # lane padding is wider than the output's sublane-padded site
        # axis, so keep the first n_sites entries
        bcol = bw_ref[pl.ds(h, 1), :].reshape(-1, 1)[:n_sites]  # (S, 1)
        contrib = jnp.where((prow > 0.0) & (row_id != h), bcol, 0.0)
        return jnp.maximum(best, contrib)

    best = jax.lax.fori_loop(0, n_sites, body, jnp.zeros_like(demand))
    if plain:
        out_ref[...] = jnp.where(best > 0.0, demand, 0.0)
    else:
        cost = sizes_ref[0, :][None, :] / best     # inf where best == 0 ...
        out_ref[...] = jnp.where(best > 0.0, demand * cost, 0.0)


@functools.partial(jax.jit, static_argnames=("plain", "interpret"))
def _value_score_call(demand, sizes, presence, bw, *, plain: bool,
                      interpret: bool):
    kernel = functools.partial(_value_score_kernel, plain=plain)
    n_sites, n_files = demand.shape
    block_f = min(_BLOCK_F, n_files)
    plane = pl.BlockSpec((n_sites, block_f), lambda j: (0, j))
    return pl.pallas_call(
        kernel,
        grid=(n_files // block_f,),
        in_specs=[plane, pl.BlockSpec((1, block_f), lambda j: (0, j)),
                  plane, pl.BlockSpec(bw.shape, lambda j: (0, 0))],
        out_specs=plane,
        out_shape=jax.ShapeDtypeStruct(demand.shape, demand.dtype),
        interpret=interpret,
    )(demand, sizes, presence, bw)


def value_score_kernel(demand, sizes, presence, bw, *, mode: str = "cost",
                       interpret: bool = False):
    """Same contract as :func:`..ref.value_score_ref`, computed by the
    Pallas kernel. Dtypes follow ``demand`` (float32 compiled on TPU,
    float64 under x64 interpret)."""
    demand = jnp.asarray(demand)
    dtype = demand.dtype
    n_sites, n_files = demand.shape
    if n_sites == 0 or n_files == 0:
        return jnp.zeros((n_sites, n_files), dtype)
    pad_s = (-n_sites) % _SUBLANES
    # whole grid blocks: lane-padded, then to a multiple of the block
    pad_f = (-n_files) % _LANES
    if n_files + pad_f > _BLOCK_F:
        pad_f = (-n_files) % _BLOCK_F
    pad_d = (-n_sites) % _LANES          # dst axis of bw rides the lanes
    demand_p = jnp.pad(demand, ((0, pad_s), (0, pad_f)))
    sizes_p = jnp.pad(jnp.asarray(sizes, dtype), (0, pad_f)).reshape(1, -1)
    presence_p = jnp.pad(jnp.asarray(presence, dtype),
                         ((0, pad_s), (0, pad_f)))
    bw_p = jnp.pad(jnp.asarray(bw, dtype), ((0, pad_s), (0, pad_d)))
    out = _value_score_call(demand_p, sizes_p, presence_p, bw_p,
                            plain=(mode == "plain"), interpret=interpret)
    return out[:n_sites, :n_files]
