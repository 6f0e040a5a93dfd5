"""End-to-end behaviour tests for the PyVertical system: the full paper
pipeline (vertical split -> PSI resolution -> dual-headed SplitNN training)
and the large-model split-training/serving drivers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.pyvertical_mnist import CONFIG as MNIST_CFG
from repro.core import MLPSplitNN, make_split_train_step, resolve
from repro.core.splitnn import train_state_init
from repro.data import make_vertical_mnist_parties
from repro.optim import multi_segment, sgd


def test_full_paper_pipeline_end_to_end():
    """Figure 2: split data -> PSI linkage + ordering -> SplitNN training.
    Uses the fast 512-bit PSI group (same protocol as production 2048)."""
    sci, owners = make_vertical_mnist_parties(300, seed=0, keep_frac=0.85)
    s_al, o_al, stats = resolve(sci, owners, group="modp512")
    assert stats["global_intersection"] == len(s_al.ids) > 150

    model = MLPSplitNN(MNIST_CFG)
    params = model.init(jax.random.PRNGKey(0))
    opt = multi_segment({"heads": sgd(MNIST_CFG.split.owner_lr),
                         "trunk": sgd(MNIST_CFG.split.scientist_lr)})
    state = train_state_init(params, opt)
    step = make_split_train_step(model.loss_fn, opt, donate=False)

    xs = jnp.asarray(np.stack([o_al["owner0"].data, o_al["owner1"].data]))
    ys = jnp.asarray(s_al.data.astype(np.int32))
    first_loss = None
    for i in range(60):
        params, state, m = step(params, state,
                                {"x_slices": xs, "labels": ys}, i)
        if first_loss is None:
            first_loss = float(m["loss"])
    assert float(m["loss"]) < first_loss * 0.7, "training did not learn"


def test_train_launcher_loss_decreases():
    from repro.launch.train import main
    loss = main(["--arch", "llama3.2-3b", "--reduced", "--steps", "30",
                 "--batch", "4", "--seq", "64", "--log-every", "29"])
    assert loss < np.log(512) * 1.05  # moved below uniform entropy


def test_serve_launcher_generates():
    from repro.launch.serve import main
    gen = main(["--arch", "llama3.2-3b", "--reduced", "--batch", "2",
                "--ctx", "32", "--new", "5"])
    assert gen.shape == (2, 5)
    assert (gen >= 0).all()


_CACHE_PROBE = r"""
import json, os
import jax, jax.numpy as jnp
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
d = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({"dir": d, "config": jax.config.jax_compilation_cache_dir,
                  "default": str(DEFAULT_DIR)}))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(from_env, tmp_path):
    """The persistent compilation cache goes where
    ``JAX_COMPILATION_CACHE_DIR`` says, and only there; without it, to
    ``.jax_cache/`` at the checkout root.  Probed in a child process, so
    this test process never turns the cache on."""
    import json
    import os
    import pathlib
    import subprocess
    import sys
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env_dir = tmp_path / "cache"
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         capture_output=True, text=True, check=True,
                         timeout=120)
    got = json.loads(out.stdout.strip().splitlines()[-1])
    root = pathlib.Path(__file__).resolve().parents[1]
    assert got["default"] == str(root / ".jax_cache")
    want = str(env_dir) if from_env else got["default"]
    assert got["dir"] == got["config"] == want
    if from_env:
        assert any(env_dir.iterdir())       # the compile landed there
