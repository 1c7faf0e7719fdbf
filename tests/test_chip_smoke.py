"""``chip_smoke.py`` off the chip: it refuses a host without a TPU, and
its phases pass end to end at a tiny size with the kernels forced onto
their x64 interpret routes (the rehearsal of the chip run)."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    if "chip_smoke" not in sys.modules:
        spec = importlib.util.spec_from_file_location("chip_smoke",
                                                      ROOT / "chip_smoke.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = mod     # its dataclass looks itself up
        spec.loader.exec_module(mod)
    return sys.modules["chip_smoke"]


def test_chip_smoke_refuses_a_host_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=tmp_path, env=env, capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    """A copy of the script alone cannot import the program: no result."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text((ROOT / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(lone)], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_chip_smoke_phases_pass_on_interpret_routes(monkeypatch, capsys):
    """Both phases at a tiny size, the flush forced onto the event_engine
    kernel's interpret route (the engine is told the platform is a TPU,
    and the op runs ``interpret`` where it is asked for ``pallas``)."""
    import jax

    import repro.kernels.event_engine as event_engine_pkg
    from repro.core import get_scenario

    chip_smoke = _load_chip_smoke()
    real_op = event_engine_pkg.event_engine

    def interpret_op(*args, backend, **kwargs):
        return real_op(*args, backend="interpret" if backend == "pallas"
                       else backend, **kwargs)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(event_engine_pkg, "event_engine", interpret_op)
    spec = dataclasses.replace(get_scenario(chip_smoke.SCENARIO),
                               tier_fanouts=(2, 2, 3))
    widths = chip_smoke.Widths(sites=13, files=40, files_evict=300, jobs=5,
                               pairs=60)
    failures = chip_smoke.smoke(spec, 60, widths, "interpret")
    assert failures == []
    records = [json.loads(line) for line in capsys.readouterr().out.splitlines()
               if line.startswith("{")]
    device_run = next(r for r in records if r.get("net") == "device")
    assert device_run["completed_jobs"] == 60
    assert device_run["net_stats"]["flush_kernel"] > 0
    assert device_run["net_stats"]["flush_host"] == 0
    kernels = [r["kernel"] for r in records if r["phase"] == "kernel"]
    assert kernels == ["st_cost", "strategy_plan", "value_score[cost]",
                       "value_score[plain]", "value_score[cost]"]
    assert all(r["ok"] for r in records if r["phase"] == "kernel")


@pytest.mark.parametrize("metric", ["avg_job_time", "avg_inter_comms"])
def test_chip_smoke_main_phase_flags_a_deviating_device_run(monkeypatch,
                                                             metric):
    """A device run whose metrics leave the 1% band, or whose flushes ran
    on the host, fails the main phase."""
    from repro.core import get_scenario
    import repro.launch.experiments as experiments

    chip_smoke = _load_chip_smoke()
    real_run = experiments.run_spec

    def skewed(spec, **kw):
        r = real_run(spec, **kw)
        if spec.net == "device":
            setattr(r, metric, getattr(r, metric) * 1.02)
        return r

    monkeypatch.setattr(experiments, "run_spec", skewed)
    spec = dataclasses.replace(get_scenario(chip_smoke.SCENARIO),
                               tier_fanouts=(2, 2, 3))
    with chip_smoke.CompileCounter() as counter:
        failures = chip_smoke.main_phase(spec, 60, counter)
    assert any(metric in f for f in failures)
    assert any("flushes on the kernel 0" in f for f in failures)
