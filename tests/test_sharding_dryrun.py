"""Small-mesh dry-run integration test: the full lower+compile path on 8
fake host devices (the production dry-run uses 512; same code path).
Runs in a subprocess because XLA_FLAGS must be set before jax init."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import dataclasses
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.launch.steps import build
from repro.launch import analysis
from repro.launch.mesh import make_host_mesh
from repro.sharding.specs import make_rules, named

arch, kind, multi_pod = "%ARCH%", "%KIND%", %MULTI%
cfg = get_config(arch, reduced=True)
if multi_pod:
    mesh = make_host_mesh(pod=2, data=2, model=2)
else:
    mesh = make_host_mesh(data=2, model=4)
shape = ShapeConfig("t", 32, 4, kind)
rules = make_rules(mesh, cfg)
fn, args, specs, donate = build(cfg, shape, mesh, rules)
lowered = jax.jit(fn, in_shardings=named(mesh, specs),
                  donate_argnums=donate).lower(*args)
compiled = lowered.compile()
mem = analysis.extract_memory(compiled)
cost = analysis.extract_cost(compiled)
colls = analysis.collective_stats(compiled.as_text(),
                                  devices_per_pod=4 if multi_pod else 0)
print("RESULT " + json.dumps({
    "flops": cost["flops"], "temp": mem["temp_bytes"],
    "coll": colls["total_bytes"], "cross": colls["cross_pod_bytes"]}))
"""


def _run(arch, kind, multi_pod):
    src = (SCRIPT.replace("%ARCH%", arch).replace("%KIND%", kind)
           .replace("%MULTI%", str(multi_pod)))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    r = subprocess.run([sys.executable, "-c", src], capture_output=True,
                       text=True, env=env, timeout=540)
    assert r.returncode == 0, f"dry-run failed:\n{r.stdout}\n{r.stderr}"
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["llama3.2-3b", "zamba2-2.7b",
                                  "mixtral-8x7b"])
def test_train_lowers_and_compiles_single_pod(arch):
    out = _run(arch, "train", False)
    assert out["flops"] > 0


@pytest.mark.slow
def test_train_lowers_multi_pod_with_owner_axis():
    out = _run("llama3.2-3b", "train", True)
    assert out["flops"] > 0
    # the pod axis exists and collectives flow
    assert out["coll"] > 0


@pytest.mark.slow
def test_decode_lowers_and_compiles():
    out = _run("llama3.2-3b", "decode", False)
    assert out["flops"] > 0
