"""The plain references against the program at tiny sizes on the CPU,
the reference's update rules and precisions, and the control: a
precision below the configuration's must fail the comparison."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, harness
from bench.reference import train as ref_train
from bench.reference.numerics import Numerics


def test_mlp_loss_matches_the_program():
    from repro.core.splitnn import MLPSplitNN
    cfg = harness.load_json("configs", "pyvertical-mnist")
    ref = harness.load_module("reference", "pyvertical-mnist")
    binding = harness.load_module("configs", "pyvertical-mnist")
    model = MLPSplitNN(binding.program_config(cfg))
    params = ref.init_params(jax.random.PRNGKey(0), cfg)
    X, y = ref.make_data(cfg, 16, seed=0)
    halves = np.stack(np.split(X.reshape(16, 28, 28), 2, -1)).reshape(
        2, 16, 392)
    with jax.default_matmul_precision("highest"):
        prog, _ = model.loss_fn(params, {"x_slices": jnp.asarray(halves),
                                         "labels": jnp.asarray(y)})
    want = ref.loss(params, halves.transpose(1, 0, 2), y, cfg,
                    Numerics("float32"))
    assert float(prog) == pytest.approx(float(want), rel=1e-6)


def test_readings_take_the_worst_leaf_and_count_moved_rows():
    ref = {"losses": [2.0, 1.0],
           "grad_norms": {"owner0": {"a": 4.0, "b": 1.0, "c": 2.0}},
           "change_norms": {"owner0": {"a": 1.0, "b": 1.0, "c": 1.0}},
           "moved_rows": {"owner0": {"table": [0, 3, 5]}}}
    prog = {"losses": [2.2, 1.0],
            "grad_norms": {"owner0": {"a": 4.0, "b": 1.5, "c": 2.0}},
            "change_norms": {"owner0": {"a": 1.0, "b": 1.0, "c": 0.5}},
            "moved_rows": {"owner0": {"table": [0, 3, 6]}}}
    r = compare.train_readings(prog, ref)
    assert r["loss_gap"] == pytest.approx(0.1)
    # leaf b: |1.5 - 1| over the median leaf's norm 2
    assert r["grad_gap"] == pytest.approx(0.25)
    assert r["change_gap"] == pytest.approx(0.5)
    # row 5 moved only in the reference, row 6 only in the program
    assert r["moved_rows_gap"] == 2


def test_moved_rows_are_the_rows_with_any_change():
    change = {"owner0": {"table": jnp.array([[0.0, 0.0], [0.0, 1e-9],
                                             [0.0, 0.0], [2.0, 0.0]]),
                         "bias": jnp.array([0.0, 1.0])}}
    assert ref_train.moved_rows(change) == {
        "owner0": {"['table']": [1, 3]}}


def test_the_mnist_control_is_not_correct(monkeypatch, capsys):
    """The paper's MLP states float32 at the ``highest`` precision; the
    reference at ``high`` (three bfloat16 passes) in the program's place
    fails the cell's limits where the program itself passes them."""
    from bench import control
    from bench.tests.rehearse import run_cell, steer
    steer(monkeypatch)
    rc, line, err = run_cell(capsys, "mnist.train-split-queue", seed=6)
    assert rc == 0 and line["correct"] is True, err[-2000:]
    cell = harness.Cell(harness.load_benchmark(), "mnist.train-split-queue")
    got = control.readings_for(cell, 6, ["control"])["control"]
    assert got["correct"] is False, got["checks"]
    assert got["checks"] == harness.judge(cell.name, got)[0]


@pytest.mark.parametrize("name, lo, hi", [
    ("float32", 0.0, 1e-6), ("high", 1e-8, 1e-4),
    ("bfloat16", 1e-4, 3e-2), ("fp8", 3e-3, 0.3)])
def test_each_precision_rounds_a_product_as_its_format_does(name, lo, hi):
    """A product's relative error against float64 lies within the
    format's rounding: each control precision is one step coarser."""
    rng = np.random.default_rng(0)
    a, b = rng.normal(size=(64, 64)), rng.normal(size=(64, 64))
    got = np.asarray(jax.jit(Numerics(name).dot)(
        jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)))
    err = np.linalg.norm(got - a @ b) / np.linalg.norm(a @ b)
    assert lo <= err <= hi, err


def test_reference_adam_clips_each_party_on_its_own():
    """Under ``adam`` with ``clip``, a party's gradient is scaled to the
    clip norm over its own leaves, and the first step moves each entry
    by the learning rate, against its gradient's sign."""
    spec = {"kind": "adam", "lr": 0.01, "b1": 0.9, "b2": 0.999,
            "eps": 1e-8, "clip": 1.0}
    init, update = ref_train._rule(spec)
    p = {"a": jnp.array([1.0, 2.0]), "b": jnp.array([0.5])}
    g = {"a": jnp.array([3.0, 0.0]), "b": jnp.array([-4.0])}
    new, state, given = update(g, init(p), p, 0.0)
    np.testing.assert_allclose(given["a"], [0.6, 0.0], rtol=1e-6)
    np.testing.assert_allclose(given["b"], [-0.8], rtol=1e-6)
    np.testing.assert_allclose(state["m"]["b"], [-0.08], rtol=1e-6)
    np.testing.assert_allclose(new["a"], [0.99, 2.0], rtol=1e-6)
    np.testing.assert_allclose(new["b"], [0.51], rtol=1e-6)
