"""``chip_smoke.py`` rehearsed on the CPU: its phases at tiny sizes, and
the script's refusal to run where JAX finds no TPU."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.configs import get_config

SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def clock(smoke):
    c = smoke.CompileClock()
    yield c
    c.close()


def test_phases_run_at_tiny_sizes(smoke, clock):
    mnist = smoke.phase_mnist(clock, n=400)
    assert mnist["steps"] >= 1 and len(mnist["losses"]) == 1
    assert mnist["compile_s"] > 0.0

    cfg = get_config("llama3.2-3b", reduced=True).replace(
        n_layers=2).with_split(cut_layer=1)
    line, session = smoke.phase_lm_train(clock, cfg, seq=64)
    assert {len(t) for t in line["losses"].values()} == {3}
    assert set(line["runs"]) == {"joint", "split", "split_int8"}

    served = smoke.phase_lm_serve(clock, session, max_new=4, n_requests=4)
    assert served["tokens_generated"] == 16
    json.dumps([mnist, line, served])       # every phase line is JSON


def test_published_widths_with_depth_and_vocab_cut(smoke):
    cfg, cuts = smoke.lm_config()
    full = get_config("llama3.2-3b")
    assert cuts == {"n_layers": [28, 2], "vocab": [128256, 32768]}
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff) == (
        full.d_model, full.n_heads, full.n_kv_heads, full.d_ff)
    assert cfg.split.cut_layer == 1


def test_refuses_to_run_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(SCRIPT)], capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout
