"""The comparison that decides ``correct`` fails what it must, on the CPU at a
tiny size: whole runs of each cell (set-up, window, reference) with the
chip check skipped and a fault planted under the timed path, and the
control (the reference itself in fp8 in the program's place). The limits are
the cells' own, set from readings at the cells' own size on the chip; at
this size a sound run reads differently, so each test also holds the broken
run against a sound run of the same seed.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_control.py
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as run_lib  # noqa: E402
from bench.tests import tiny  # noqa: E402

TRAIN = ["train-pamm-4k", "train-exact-4k"]
SERVE = ["serve-chat-poisson", "serve-decode-heavy"]
_SOUND: dict = {}


def _numbers(name, **kw):
    import jax

    args = tiny.args(name, seconds=0.5)
    for k, v in kw.items():
        setattr(args, k, v)
    out = run_lib.kind_run(tiny.cell(name), args, jax.devices()[:1])
    limits = tiny.cell(name)["settings"]["limits"]
    return out, {k: (out["numbers"][k], limits[k]) for k in limits}


def _sound(name):
    if name not in _SOUND:
        _SOUND[name] = _numbers(name)[1]
    return _SOUND[name]


@pytest.mark.parametrize("name,fault", [(n, f) for n in TRAIN
                                        for f in ("unchanged", "half_batch")]
                         + [(n, "token") for n in SERVE])
def test_fault_makes_correct_false(name, fault):
    sound = _sound(name)
    _, broken = _numbers(name, fault=fault)
    failing = [k for k, (v, lim) in broken.items()
               if v > lim and v >= 10 * max(sound[k][0], 1e-9)]
    assert failing, (broken, sound)


@pytest.mark.parametrize("name", TRAIN + SERVE)
def test_control_fails_a_number(name):
    sound = _sound(name)
    out, _ = _numbers(name, control=True)
    failing = [k for k, v in out["control"].items()
               if v > sound[k][1] and v >= 3 * max(sound[k][0], 1e-9)]
    assert failing, (out["control"], sound)
