"""DeepSeek-V2-Lite's program against its plain reference at a tiny size
on the CPU: the MLA block with YaRN, the expert share (and no dropped
choice when every token picks one expert), and one split ``fit`` step
through ``VerticalSession``; then the cell's comparison against its
planted faults and its control."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference.numerics import Numerics
from bench.tests.rehearse import run_cell, steer, tiny

WORKLOAD = "dsv2lite.train-split-s4k"
CONFIG = "deepseek-v2-lite"


@pytest.fixture(scope="module")
def cfg():
    """The rehearsal sizes, computed in float32 (the reference's
    precision) so that only the order of operations differs."""
    c = tiny(harness.load_json)("configs", CONFIG)
    c["compute_dtype"] = "float32"
    return c


@pytest.fixture(scope="module")
def ref():
    return harness.load_module("reference", CONFIG)


@pytest.fixture(scope="module")
def arch(cfg):
    return harness.load_module("configs", CONFIG).program_config(cfg)


@pytest.fixture(scope="module")
def params(cfg, ref):
    return ref.init_params(jax.random.PRNGKey(0), cfg)


def test_the_reference_has_the_programs_layout(arch, cfg, ref):
    from repro.models.model import SplitModel
    want = jax.eval_shape(SplitModel(arch).init, jax.random.PRNGKey(0))
    got = jax.eval_shape(functools.partial(ref.init_params, cfg=cfg),
                         jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), want) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), got)


def test_yarn_of_the_reference_is_the_programs(cfg, arch, ref):
    from repro.models import attention, layers
    full = harness.load_json("configs", CONFIG)
    full_arch = harness.load_module("configs", CONFIG).program_config(full)
    freqs, factor, scale = ref.yarn(full)
    np.testing.assert_allclose(np.asarray(freqs), np.asarray(
        layers.yarn_freqs(64, full_arch.rope_theta, full_arch.yarn)),
        rtol=1e-6)
    assert factor == 1.0
    assert scale == pytest.approx(attention.mla_scale(full_arch), rel=1e-12)
    assert scale == pytest.approx(0.1147, abs=5e-5)
    assert ref.yarn(cfg)[2] == pytest.approx(attention.mla_scale(arch),
                                             rel=1e-12)


@pytest.mark.parametrize("offset", [0, 40, 4000])
def test_mla_block_with_yarn_matches_the_reference(cfg, arch, ref, params,
                                                   offset):
    """The program's MLA sub-layer (latent, norm, shared rotary key,
    up-projection, YaRN, causal attention, output projection) on a
    sequence at global positions ``offset...``."""
    from repro.models import attention
    p = jax.tree.map(lambda a: a[0], params["trunk"]["blocks"]["units"][
        "b0"]["attn"])
    S = 48
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, cfg["hidden_size"]))
    pos = offset + jnp.arange(S)
    with jax.default_matmul_precision("highest"):
        got, _ = attention.attn_apply(p, x, cfg=arch, kind="causal",
                                      positions=pos, chunk=16)
        want = ref._mla(p, x[0], pos, ref._dims(cfg), cfg,
                        Numerics("float32"))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def _moe_dims(ref, cfg):
    return dict(ref._dims(cfg), alpha=cfg["aux_loss_alpha"])


def test_the_expert_share_matches_the_reference(cfg, arch, ref, params):
    """The held experts' grouped computation, the shared experts and the
    sequence-wise balance loss against the reference's dense share."""
    from repro.models import moe
    p = jax.tree.map(lambda a: a[1], params["trunk"]["blocks"]["units"][
        "b0"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 64, cfg["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        got, aux = moe.moe_apply(p, x, arch.moe, "swiglu")
        want, want_aux = ref._moe(p, x[0], _moe_dims(ref, cfg),
                                  Numerics("float32"))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert float(aux) == pytest.approx(float(want_aux), rel=1e-5)


def test_no_choice_is_dropped_when_every_token_picks_one_expert(
        cfg, arch, ref, params):
    """A router biased so that every token's first choice is held expert
    0: the share layer still equals the reference, where the capacity
    layer (every expert held, capacity 1.25 x the even load) drops."""
    import dataclasses
    from repro.models import moe
    p = jax.tree.map(lambda a: a[0], params["trunk"]["blocks"]["units"][
        "b0"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64, cfg["hidden_size"]))
    x = x.at[..., 0].set(10.0)
    p = dict(p, router={"w": p["router"]["w"].at[0, 0].set(5.0)})
    with jax.default_matmul_precision("highest"):
        _, _, top_e = moe.route(x[0], p["router"], arch.moe)
        assert bool(jnp.all(top_e[:, 0] == 0))
        got, _, stats = moe.moe_apply(p, x, arch.moe, "swiglu",
                                      with_stats=True)
        want, _ = ref._moe(p, x[0], _moe_dims(ref, cfg), Numerics("float32"))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)
        assert float(stats["moe_peak"]) == 64
        # the capacity layer holding every expert drops most of them
        E = arch.moe.n_experts
        full = dict(p)
        for k in ("w_in", "w_gate", "w_out"):
            full[k] = jnp.concatenate(
                [p[k], jnp.zeros((E - p[k].shape[0],) + p[k].shape[1:])])
        cap = dataclasses.replace(arch.moe, n_held=0)
        dropped, _ = moe.moe_apply(full, x, cap, "swiglu")
    assert not np.allclose(np.asarray(dropped[0]), np.asarray(want),
                           rtol=1e-3, atol=1e-3)


def test_one_split_step_matches_the_reference(cfg, arch, ref):
    """One ``fit(mode="split", backend="queue")`` step through
    ``VerticalSession``: its loss and each party's first gradient (as
    its optimizer got it) against the reference's, in float32."""
    from repro.federation import VerticalSession
    from bench.drivers.train import FirstUpdate, index_batches
    from bench.drivers.train_large import run_steps
    traffic = tiny(harness.load_json)("traffic", "dsv2lite-split-s4k")
    binding = harness.load_module("configs", CONFIG)
    with jax.default_matmul_precision("highest"):
        data = binding.Data(cfg, traffic, 5, ref)
        session = VerticalSession(data.scientist, data.owners, seed=5)
        session.resolve(group="modp512")
        session.build(arch, seed=0)
        session.params = ref.init_params(jax.random.PRNGKey(5), cfg)
        rec = FirstUpdate(session.adapter, cfg["optimizer"]).install()
        try:
            hist = session.fit(steps=1, batch_size=traffic["batch"],
                               mode="split", backend="queue",
                               verbose=False)
        finally:
            rec.uninstall()
        want = run_steps(ref, cfg, ref.init_params(jax.random.PRNGKey(5),
                                                   cfg),
                         [data.reference_batch(pos) for pos in index_batches(
                             5, len(data.aligned), traffic["batch"], 1)],
                         Numerics("float32"))
    assert hist["train"][0]["loss"] == pytest.approx(want["losses"][0],
                                                     rel=1e-5)
    for party, leaves in want["grad_norms"].items():
        for leaf, norm in leaves.items():
            assert rec.records[party][leaf] == pytest.approx(
                norm, rel=1e-3, abs=1e-7), (party, leaf)
    moe_stats = session.transport_stats["moe"]
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert moe_stats["steps"] == 1
    assert 0 < moe_stats["routed"] <= n_moe * traffic["batch"] \
        * traffic["seq_len"] * cfg["num_experts_per_tok"]
    assert moe_stats["rows_per_routed"] >= 1.0


def test_the_lean_reference_steps_are_the_shared_ones(cfg, ref):
    """``train_large.run_steps`` (one row at a time, the optimizer state
    on the host) takes the steps of ``bench.reference.train.run_steps``
    and gives its readings."""
    from bench.drivers.train_large import run_steps
    from bench.reference import train as ref_train
    traffic = tiny(harness.load_json)("traffic", "dsv2lite-split-s4k")
    tokens, labels = ref.make_data(cfg, 4, 3, traffic["seq_len"],
                                   traffic["zipf_s"])
    batches = [(tokens[:2], labels[:2]), (tokens[2:], labels[2:])]
    got, want = (fn(ref, cfg, ref.init_params(jax.random.PRNGKey(3), cfg),
                    batches, Numerics("float32"))
                 for fn in (run_steps, ref_train.run_steps))
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-5)
    for key in ("grad_norms", "change_norms"):
        for party, leaves in want[key].items():
            for leaf, norm in leaves.items():
                assert got[key][party][leaf] == pytest.approx(
                    norm, rel=1e-4, abs=1e-8), (key, party, leaf)
    assert got["moved_rows"] == want["moved_rows"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered"])
def test_a_broken_step_is_not_correct(fault, monkeypatch, capsys):
    from bench.tests.test_bench_faults import FAULTS
    steer(monkeypatch)
    FAULTS[fault](monkeypatch)
    rc, line, err = run_cell(capsys, WORKLOAD, seed=5)
    assert rc == 0, err[-2000:]
    assert line["correct"] is False, line["checks"]


def test_the_control_is_not_correct(monkeypatch):
    """The configuration states bfloat16 compute; the reference in fp8
    (``control_precision``) in the program's place fails the cell's
    limits, and so do both planted faults of the reference."""
    from bench import control
    from bench.drivers.train_large import lean_reference
    steer(monkeypatch)
    cell = harness.Cell(harness.load_benchmark(), WORKLOAD)
    with lean_reference():
        got = control.readings_for(cell, 6,
                                   ["control", "half_batch", "altered"])
    for variant, r in got.items():
        assert r["correct"] is False, (variant, r["checks"])
