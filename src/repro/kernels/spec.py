"""Kernel registry spec — the uniform shape every kernel package exports.

Each package under :mod:`repro.kernels` (``event_engine``, ``st_cost``,
``strategy_plan``, ``value_score``, ``selective_scan``,
``flash_attention``) exposes a module-level ``SPEC: KernelSpec`` in its
``__init__``. The spec is the machine-readable contract the jaxpr
auditor (:mod:`repro.analysis`) enforces for *every* kernel instead of
the old one-off ``st_cost`` shape-guard test:

* ``max_rank`` — structural rank cap on every intermediate aval in the
  traced jaxpr. For the sim kernels this is 2 (the whole point of the
  blocked formulations is never materializing the
  ``(sites, files, sites)`` / ``(jobs, files, sites)`` broadcasts); for
  the model kernels it is 3/4 (their *inputs* are rank 3/4 — the banned
  ``(B, S, D, N)`` scan blow-up and ``(B, H, Sq, Skv)`` logits plane are
  caught by rank and byte budget respectively).
* ``budget_bytes`` — per-eqn peak-intermediate budget: for each equation
  in the jaxpr (pallas bodies included) the auditor sums the aval bytes
  of its operands and results; the max over equations must stay under
  budget at the spec's representative audit shapes.
* ``make_inputs`` — builds the representative-shape numpy inputs the
  audit traces at (float32; int32 where the operand is an index or a
  rank), plus the kernel's static kwargs.
* ``make_small_inputs`` — optional small-shape inputs for the runtime
  oracle checks (float64 oracle dtype + x64-interpret bit-identity).
  Only sim kernels whose entry point shares its oracle's signature
  carry these: their refs are pure-numpy oracles (not traceable), so
  dtype discipline is checked by execution.
* ``arg_units`` / ``out_units`` — per-operand dimension signature in the
  vocabulary of :mod:`repro.analysis.units` (``bytes``, ``bytes_per_s``,
  ``sim_seconds``, ``count``, ``score``; model-kernel tensors are
  dimensionless ``score``). The jaxpr auditor asserts every spec
  declares a complete, valid signature and records it in
  ``results/ANALYSIS_kernels.json``.

This module is imported by every kernel ``__init__`` and therefore MUST
stay jax-free (the DES engine imports kernel packages on hosts without
jax installed); an input builder that needs a kernel's own host staging
imports it when it is called.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable

import numpy as np

#: (positional args, static kwargs) pair produced by input builders.
InputCase = tuple[tuple, dict]


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Registry entry for one kernel package.

    Attributes:
      name: registry key, matches the package name.
      module: import path of the package (``repro.kernels.<name>``).
      kernel_attr: entry point in ``<module>.kernel`` taking an
        ``interpret=`` kwarg (the raw pallas_call wrapper the auditor
        traces).
      ref_attr: oracle in ``<module>.ref``.
      domain: ``"sim"`` (host-facing DES op, float64 numpy oracle,
        bit-identity contract) or ``"model"`` (jitted device op, jnp
        reference, tolerance contract).
      max_rank: max allowed aval rank anywhere in the traced jaxpr.
      budget_bytes: per-eqn peak intermediate-bytes budget at the audit
        shapes (float32 trace).
      make_inputs: audit-shape input builder.
      make_small_inputs: small-shape builder for runtime oracle checks
        (sim kernels only; ``None`` for model kernels whose identity
        contract lives in tests/test_kernels.py tolerances).
      multi_output: kernel returns a tuple rather than one array.
      arg_units: dimension per positional argument of ``make_inputs``.
      out_units: dimension per output (one entry when single-output).
    """

    name: str
    module: str
    kernel_attr: str
    ref_attr: str
    domain: str
    max_rank: int
    budget_bytes: int
    make_inputs: Callable[[], InputCase]
    make_small_inputs: Callable[[], InputCase] | None = None
    multi_output: bool = False
    arg_units: tuple[str, ...] = ()
    out_units: tuple[str, ...] = ()

    def load_kernel(self) -> Callable[..., Any]:
        """Import and return the raw kernel entry point (needs jax)."""
        mod = importlib.import_module(self.module + ".kernel")
        return getattr(mod, self.kernel_attr)

    def load_ref(self) -> Callable[..., Any]:
        """Import and return the reference/oracle implementation."""
        mod = importlib.import_module(self.module + ".ref")
        return getattr(mod, self.ref_attr)


# ---------------------------------------------------------------------------
# Input builders. Shapes mirror the "representative" parametrizations in
# tests/test_kernels.py (paper grid = 52 sites x 100 files, bulk bursts of
# 50 jobs) so budget numbers line up with what the tests exercise. All
# builders are seeded and pure numpy.
# ---------------------------------------------------------------------------


def _event_engine_inputs(slots: int, links: int, levels: int,
                         seed: int = 3) -> InputCase:
    """The two operands of ``_flush_call`` as the engine's route builds
    them on the host (``host_inputs``): the padded, transposed int32
    path and the int32 share ranks."""
    from .event_engine.kernel import host_inputs  # deferred: jax
    rng = np.random.default_rng(seed)
    path = np.where(rng.random((slots, levels)) < 0.35, -1,
                    rng.integers(0, links, (slots, levels)))
    path[:, 0] = rng.integers(0, links, slots)
    path[rng.random(slots) < 0.25] = -1       # released slots
    bw = rng.random(links) * 1e8 + 1e5
    act = rng.integers(0, 12, links).astype(np.float64)
    inputs, _ = host_inputs(path, bw, act)
    return inputs, {}


def _value_score_inputs(sites: int, files: int, seed: int = 2) -> InputCase:
    rng = np.random.default_rng(seed)
    demand = (rng.random((sites, files)) * 20.0).astype(np.float32)
    sizes = (rng.random(files) * 1e9 + 1e6).astype(np.float32)
    presence = rng.random((sites, files)) < 0.25
    presence[0, :] = True
    bw = (rng.random((sites, sites)) * 1.25e8 + 1e5).astype(np.float32)
    return ((demand, sizes, presence.astype(np.float32), bw),
            {"mode": "cost"})


def _st_cost_inputs(sites: int, files: int, jobs: int,
                    seed: int = 2) -> InputCase:
    rng = np.random.default_rng(seed)
    bw = rng.random((sites, sites)) * 1.25e8 + 1e5
    presence = rng.random((sites, files)) < 0.2
    presence[0, :] = True
    online = rng.random(sites) < 0.85
    online[0] = True
    fetch_mask = presence & online[:, None]
    fetch_mask[0, :] = presence[0, :]
    sizes = rng.random(files) * 1e9 + 1e6
    required = rng.random((jobs, files)) < min(0.5, 12.0 / files)
    rel = rng.random(sites) * 50.0
    args = tuple(np.asarray(a, np.float32)
                 for a in (bw, fetch_mask, presence, sizes, required, rel,
                           online))
    return (args, {})


def _strategy_plan_inputs(sites: int, pairs: int, seed: int = 2) -> InputCase:
    rng = np.random.default_rng(seed)
    bw = rng.random((sites, pairs)) * 1.25e8 + 1e5
    fetch = rng.random((sites, pairs)) < 0.15
    fetch[rng.integers(0, sites, pairs), np.arange(pairs)] = True
    # region-block structure: contiguous site ranges share a region, each
    # pair's destination region is one of them
    n_regions = max(2, sites // 8)
    region = np.arange(sites) * n_regions // sites
    local = region[:, None] == rng.integers(0, n_regions, pairs)[None, :]
    serve = np.where(rng.random(sites) < 0.5, rng.random(sites) * 9.0, 0.0)
    size = rng.random(pairs) * 1e9 + 1e6
    free = np.where(rng.random(pairs) < 0.5,
                    rng.random(pairs) * 2e9, rng.random(pairs) * 1e8)
    args = tuple(np.asarray(a, np.float32)
                 for a in (bw, fetch, local, serve, free, size))
    return (args, {})


def _selective_scan_inputs(Bz: int, S: int, Di: int, N: int,
                           seed: int = 2) -> InputCase:
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((Bz, S, Di)).astype(np.float32)
    dt = (np.log1p(np.exp(rng.standard_normal((Bz, S, Di)))) * 0.1
          ).astype(np.float32)
    B = rng.standard_normal((Bz, S, N)).astype(np.float32)
    C = rng.standard_normal((Bz, S, N)).astype(np.float32)
    A = (-np.exp(rng.standard_normal((Di, N)))).astype(np.float32)
    D = rng.standard_normal(Di).astype(np.float32)
    h0 = np.zeros((Bz, Di, N), np.float32)
    return ((x, dt, B, C, A, D, h0), {"chunk": 64, "block_d": 128})


def _flash_attention_inputs(B: int, H: int, KV: int, Sq: int, Skv: int,
                            hd: int, seed: int = 2) -> InputCase:
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((B, H, Sq, hd)).astype(np.float32)
    k = rng.standard_normal((B, KV, Skv, hd)).astype(np.float32)
    v = rng.standard_normal((B, KV, Skv, hd)).astype(np.float32)
    return ((q, k, v), {"causal": True, "block_q": 128, "block_k": 128})


#: Budgets are ~1.25x the measured per-eqn peak at the audit shapes and
#: sit well below the banned dense materializations (see docs/ANALYSIS.md
#: for the per-kernel headroom math). Keep in sync with
#: results/ANALYSIS_kernels.json (regenerated by ``python -m
#: repro.analysis``).
#: The flush's device program, as the chip runs it: the float64 oracle
#: identity of the whole route (stage, program, settle) is held at the op
#: level by tests/test_kernels.py, so there is no small-input check here.
EVENT_ENGINE_SPEC = KernelSpec(
    name="event_engine", module="repro.kernels.event_engine",
    kernel_attr="_flush_call", ref_attr="event_engine_ref",
    domain="sim", max_rank=2, budget_bytes=20_000,
    make_inputs=lambda: _event_engine_inputs(256, 60, 5),
    arg_units=("count", "count"),
    out_units=("count",),
)

ST_COST_SPEC = KernelSpec(
    name="st_cost", module="repro.kernels.st_cost",
    kernel_attr="st_cost_kernel", ref_attr="st_cost_ref",
    domain="sim", max_rank=2, budget_bytes=450_000,
    make_inputs=lambda: _st_cost_inputs(52, 100, 50),
    make_small_inputs=lambda: _st_cost_inputs(8, 24, 5),
    arg_units=("bytes_per_s", "count", "count", "bytes", "count",
               "score", "count"),
    out_units=("sim_seconds",),
)

STRATEGY_PLAN_SPEC = KernelSpec(
    name="strategy_plan", module="repro.kernels.strategy_plan",
    kernel_attr="strategy_plan_kernel", ref_attr="strategy_plan_ref",
    domain="sim", max_rank=2, budget_bytes=1_100_000,
    make_inputs=lambda: _strategy_plan_inputs(500, 50),
    make_small_inputs=lambda: _strategy_plan_inputs(24, 7),
    multi_output=True,
    arg_units=("bytes_per_s", "count", "count", "score", "bytes",
               "bytes"),
    out_units=("count", "count", "count", "count", "count"),
)

VALUE_SCORE_SPEC = KernelSpec(
    name="value_score", module="repro.kernels.value_score",
    kernel_attr="value_score_kernel", ref_attr="value_score_ref",
    domain="sim", max_rank=2, budget_bytes=200_000,
    make_inputs=lambda: _value_score_inputs(52, 100),
    make_small_inputs=lambda: _value_score_inputs(13, 20),
    arg_units=("score", "bytes", "count", "bytes_per_s"),
    out_units=("score",),
)

SELECTIVE_SCAN_SPEC = KernelSpec(
    name="selective_scan", module="repro.kernels.selective_scan",
    kernel_attr="selective_scan_kernel", ref_attr="selective_scan_ref",
    domain="model", max_rank=3, budget_bytes=2_200_000,
    make_inputs=lambda: _selective_scan_inputs(1, 512, 256, 16),
    multi_output=True,
    arg_units=("score", "score", "score", "score", "score",
               "score", "score"),
    out_units=("score", "score"),
)

FLASH_ATTENTION_SPEC = KernelSpec(
    name="flash_attention", module="repro.kernels.flash_attention",
    kernel_attr="flash_attention_kernel", ref_attr="flash_attention_ref",
    domain="model", max_rank=4, budget_bytes=1_700_000,
    make_inputs=lambda: _flash_attention_inputs(1, 2, 2, 256, 1024, 64),
    arg_units=("score", "score", "score"),
    out_units=("score",),
)
