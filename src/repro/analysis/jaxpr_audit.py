"""jaxpr auditor — structural invariants for every registered kernel.

Generalizes the one-off ``st_cost`` rank-3 shape-guard test to every
kernel package discovered by :func:`repro.kernels.registered_kernels`.
For each kernel spec the auditor traces the raw kernel entry point
(``interpret=True``, so the pallas_call body is abstractly evaluated
too) at the spec's representative float32 shapes and walks the full
jaxpr, nested sub-jaxprs included:

* **rank** — no intermediate aval exceeds ``spec.max_rank``. For the
  sim kernels that bans any ``(sites, files, sites)`` /
  ``(jobs, files, sites)`` rank-3 broadcast anywhere; for
  ``selective_scan`` (rank cap 3) it bans the ``(B, S, D, N)`` dense
  scan blow-up.
* **dtype** — a float32 trace contains no float64 avals: device
  execution is f32 by contract, f64 belongs to the oracles and the x64
  interpret route only.
* **callbacks** — no host-callback primitives inside the traced
  computation (``pure_callback``, ``io_callback``, ``debug_callback``,
  ``custom_partitioning`` call-outs): host round-trips inside jit break
  both determinism and TPU performance.
* **budget** — per-eqn peak intermediate bytes: for each equation, sum
  the aval bytes of operands + results; the max over equations must
  stay <= ``spec.budget_bytes``. Constants/literals count at their aval
  size; the estimate is deliberately simple and conservative — it
  exists to catch order-of-magnitude regressions (a materialized
  logits plane, a dense scan state), not to model XLA buffer reuse.
* **units** — every spec declares a complete per-operand dimension
  signature (``arg_units`` one entry per ``make_inputs`` arg,
  ``out_units`` nonempty, vocabulary :data:`repro.analysis.units.
  DIMENSIONS`); the signature is recorded alongside the structural
  evidence so the JSON doubles as the kernels' unit registry.

Runtime oracle checks (sim kernels, ``make_small_inputs``):

* the float64 numpy oracle returns float64 (dtype discipline), and
* the kernel under x64 interpret mode is **bit-identical** to it — the
  same contract the golden suite pins end-to-end.

Results (measured peaks, budgets, verdicts) are written to
``results/ANALYSIS_kernels.json`` so CI archives the audit evidence.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any

import numpy as np

from .units import DIMENSIONS

#: substrings identifying host-callback primitives in any jax version
CALLBACK_PRIMITIVES = ("callback", "outside_call", "host_call",
                      "infeed", "outfeed")


def check_unit_signature(spec, n_args: int) -> bool:
    """True when the spec's dimension signature is complete and valid.

    jax-free (operates on the spec alone) so the kernels-interpret CI
    job can assert it without tracing.
    """
    arg_units = tuple(getattr(spec, "arg_units", ()))
    out_units = tuple(getattr(spec, "out_units", ()))
    return (len(arg_units) == n_args
            and len(out_units) > 0
            and all(u in DIMENSIONS for u in arg_units + out_units))


def _iter_eqns(jaxpr):
    """Yield every equation, recursing into nested jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    yield from _iter_eqns(inner)
                elif hasattr(sub, "eqns"):
                    yield from _iter_eqns(sub)


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def audit_kernel(spec) -> dict[str, Any]:
    """Audit one kernel spec. Returns a JSON-ready report dict."""
    import jax

    kernel = spec.load_kernel()
    args, kwargs = spec.make_inputs()
    jaxpr = jax.make_jaxpr(
        lambda *a: kernel(*a, **kwargs, interpret=True))(*args)

    max_rank = 0
    peak_bytes = 0
    peak_eqn = ""
    bad_dtypes: list[str] = []
    callbacks: list[str] = []
    n_eqns = 0
    for eqn in _iter_eqns(jaxpr.jaxpr):
        n_eqns += 1
        prim = eqn.primitive.name
        if any(s in prim for s in CALLBACK_PRIMITIVES):
            callbacks.append(prim)
        eqn_bytes = 0
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "shape"):
                continue
            max_rank = max(max_rank, len(aval.shape))
            eqn_bytes += _aval_bytes(aval)
            dtype = getattr(aval, "dtype", None)
            if dtype is not None and np.dtype(dtype) == np.float64:
                bad_dtypes.append(f"{prim}: {aval}")
        if eqn_bytes > peak_bytes:
            peak_bytes, peak_eqn = eqn_bytes, prim

    checks = {
        "rank_ok": max_rank <= spec.max_rank,
        "budget_ok": peak_bytes <= spec.budget_bytes,
        "no_callbacks": not callbacks,
        "f32_trace_has_no_f64": not bad_dtypes,
        "units_declared": check_unit_signature(spec, len(args)),
    }
    report: dict[str, Any] = {
        "domain": spec.domain,
        "audit_shapes": [list(np.shape(a)) for a in args],
        "arg_units": list(getattr(spec, "arg_units", ())),
        "out_units": list(getattr(spec, "out_units", ())),
        "n_eqns": n_eqns,
        "max_rank": max_rank,
        "max_rank_allowed": spec.max_rank,
        "peak_eqn_bytes": peak_bytes,
        "peak_eqn_primitive": peak_eqn,
        "budget_bytes": spec.budget_bytes,
        "callbacks": sorted(set(callbacks)),
        "f64_avals_in_f32_trace": bad_dtypes[:5],
    }

    if spec.make_small_inputs is not None:
        report["oracle"] = _audit_oracle(spec)
        checks["oracle_f64"] = report["oracle"]["returns_float64"]
        checks["x64_interpret_identity"] = \
            report["oracle"]["interpret_bit_identical"]

    report["checks"] = checks
    report["ok"] = all(checks.values())
    return report


def _audit_oracle(spec) -> dict[str, Any]:
    """Runtime dtype + bit-identity checks for a sim kernel's oracle."""
    import jax

    ref = spec.load_ref()
    kernel = spec.load_kernel()
    args, kwargs = spec.make_small_inputs()
    args64 = tuple(np.asarray(a, np.float64)
                   if np.asarray(a).dtype.kind == "f" else np.asarray(a)
                   for a in args)
    ref_out = ref(*args64, **kwargs)
    ref_flat = (ref_out if isinstance(ref_out, tuple) else (ref_out,))
    returns_f64 = all(
        np.asarray(r).dtype == np.float64 or np.asarray(r).ndim == 0
        for r in ref_flat)

    with jax.enable_x64(True):
        k_out = kernel(*args64, **kwargs, interpret=True)
    k_flat = (k_out if isinstance(k_out, tuple) else (k_out,))
    identical = len(k_flat) == len(ref_flat) and all(
        np.array_equal(np.asarray(a, np.float64), np.asarray(b, np.float64))
        for a, b in zip(k_flat, ref_flat))
    return {"returns_float64": bool(returns_f64),
            "interpret_bit_identical": bool(identical)}


def run_jaxpr_audit(json_path: Path | str | None = None
                    ) -> tuple[dict[str, Any], list[str]]:
    """Audit every registered kernel.

    Returns ``(report, failures)`` where failures is a list of
    human-readable failed-check strings (empty = all pass). Writes the
    report JSON to ``json_path`` when given.
    """
    from repro.kernels import registered_kernels

    kernels: dict[str, Any] = {}
    report: dict[str, Any] = {"kernels": kernels}
    failures: list[str] = []
    for name, spec in registered_kernels().items():
        entry = audit_kernel(spec)
        kernels[name] = entry
        for check, ok in entry["checks"].items():
            if not ok:
                failures.append(f"{name}: {check} failed "
                                f"(peak={entry['peak_eqn_bytes']}B, "
                                f"rank={entry['max_rank']}, "
                                f"callbacks={entry['callbacks']})")
    if json_path is not None:
        path = Path(json_path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(report, indent=2, sort_keys=True) + "\n")
    return report, failures
