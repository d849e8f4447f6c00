"""CPU rehearsal of ``chip_smoke.py``.

The smoke script runs only on a TPU.  These tests run its phase functions
on the CPU instead — qwen2.5-3b's family cut to the reduced width but kept
in bf16, the kernels in interpret mode (chosen by platform), a trace of a
few short requests — so a wrong path, argument or gate shows here before
it costs chip time.  The platform check is steered from inside the test;
the script has no option for it.
"""

import importlib.util
import json
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclasses resolve names there
    spec.loader.exec_module(mod)
    return mod


def _tiny(smoke):
    cfg = get_config(smoke.ARCH).reduced().replace(dtype="bfloat16")
    dims = smoke.Dims(slots=2, max_len=64, block=8, chunk=16, prefix=8,
                      min_prompt=12, max_prompt=40, max_new=4, requests=3)
    return cfg, dims


def test_one_chip_phases_on_cpu(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "PLATFORM", jax.default_backend())
    device = smoke.check_device()
    assert device == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                      "count": len(jax.devices())}

    # interpret-mode kernels never become TPU kernels, so record what the
    # gate was asked instead of running it
    asked = {}
    gate = smoke.require_kernels

    def record(label, programs, expected):
        assert all(isinstance(t, str) and t for t in programs)
        asked[label] = (programs, expected)

    monkeypatch.setattr(smoke, "require_kernels", record)
    cfg, dims = _tiny(smoke)
    smoke.one_chip(cfg, dims)
    out = capsys.readouterr().out

    for phase in ("phase 1: kernel parity", "phase 2: dense model over HTTP",
                  "phase 3: factorized model"):
        assert phase in out
    assert f"served {dims.requests}/{dims.requests}, errors 0" in out
    assert "blocks in use after drain 0" in out
    assert asked["dense"][1] == {"decode": ["_paged_attention_call"],
                                 "prefill": ["_chunk_attention_call"]}
    assert "led_matmul_2d" in asked["factorized"][1]["decode"]
    assert "led_matmul_2d" in asked["factorized"][1]["prefill"]
    # the real gate refuses programs whose kernels were interpreted
    for label, (programs, expected) in asked.items():
        with pytest.raises(smoke.SmokeFailure, match="lacks"):
            gate(label, programs, expected)


def test_kernel_parity_gate_fails_past_tolerance(smoke, monkeypatch):
    cfg, dims = _tiny(smoke)
    monkeypatch.setattr(smoke, "TOL", -1.0)  # no error is within it
    with pytest.raises(smoke.SmokeFailure, match="error"):
        smoke.kernel_parity(cfg, dims)


def test_main_exits_nonzero_off_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    for line in out.splitlines():  # no result line
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
