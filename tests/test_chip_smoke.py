"""chip_smoke.py: refuses to run without a TPU, and its phases hold
together end to end at smoke size on the CPU.

The phases are steered here, in the test: the CPU stands in for the TPU,
the smoke sibling of internlm2-1.8b for the full widths, and the kernel
name check is stubbed — interpret mode lowers no Mosaic kernel to name.
"""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture
def smoke(monkeypatch):
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    monkeypatch.setattr(mod, "ARCH", "internlm2-1.8b_smoke")
    monkeypatch.setattr(mod, "SEQ_LEN", 32)
    monkeypatch.setattr(mod, "PROMPT_LEN", 24)
    monkeypatch.setattr(mod, "GEN_LEN", 5)
    monkeypatch.setattr(mod, "tpu_devices", lambda n: jax.devices()[:n])
    monkeypatch.setattr(mod, "kernel_names",
                        lambda lowered: mod.TRAIN_KERNELS | {"paged_decode"})
    monkeypatch.setattr(mod, "peak_bytes", lambda device: 0)
    from repro.launch import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "off in this test")
    return mod


def test_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(SCRIPT)], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_one_chip_phases_at_smoke_size(smoke, capsys):
    smoke.main([])
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    phases = [json.loads(l.split(" ", 4)[-1]) for l in lines
              if l.startswith("bring-up")]
    assert [p["phase"] for p in phases] == ["train", "serve"]
    assert len(phases[0]["losses"]) == smoke.TRAIN_STEPS
    assert phases[1]["prefill_rel_l2_max"] <= smoke.PREFILL_REL_L2
