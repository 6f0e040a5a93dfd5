"""DeepSeek-V2-Lite's mechanisms in the program: YaRN against its closed
form, the expert share layer (shares add up to the uncut layer, its
counters), the leading dense layer, and the serving engine's refusal of
multi-head latent attention."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MoEConfig
from repro.models import attention, layers, moe as moe_mod
from repro.models.model import SplitModel


def test_yarn_frequencies_and_scale_match_the_closed_form():
    """At DeepSeek-V2-Lite's settings the correction range is slots
    floor(10.47) = 10 to ceil(22.51) = 23 of 32; slots below it keep
    theta^(-2i/64), slots above it are divided by 40, a linear ramp
    between; the cos/sin factor is 1 and the softmax scale
    192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2 = 0.1147."""
    cfg = get_config("deepseek-v2-lite")
    y = cfg.yarn
    assert layers.yarn_correction_range(64, cfg.rope_theta, y) == (10, 23)
    freqs = np.asarray(layers.yarn_freqs(64, cfg.rope_theta, y))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(freqs[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], plain[23:] / 40, rtol=1e-6)
    ramp = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(
        freqs[11:23], plain[11:23] * (1 - ramp) + plain[11:23] / 40 * ramp,
        rtol=1e-6)
    assert layers.yarn_mscale(y.factor, y.mscale) \
        / layers.yarn_mscale(y.factor, y.mscale_all_dim) == 1.0
    assert attention.mla_scale(cfg) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2, rel=1e-12)
    assert attention.mla_scale(cfg) == pytest.approx(0.1147, abs=5e-5)


def test_published_sizes_and_parameter_count():
    c = get_config("deepseek-v2-lite")
    assert (c.n_layers, c.d_model, c.n_heads, c.vocab, c.d_ff) == (
        27, 2048, 16, 102400, 10944)
    assert (c.mla.kv_lora_rank, c.mla.qk_nope_head_dim,
            c.mla.qk_rope_head_dim, c.mla.v_head_dim) == (512, 128, 64, 128)
    assert (c.moe.n_experts, c.moe.top_k, c.moe.n_shared, c.moe.d_expert,
            c.n_dense_layers) == (64, 6, 2, 1408, 1)
    assert not c.moe.norm_topk and c.moe.aux_kind == "seq"
    # 15.7B parameters (DeepSeek-V2-Lite's model card)
    assert c.param_count() / 1e9 == pytest.approx(15.7, abs=0.05)
    model = SplitModel(c.reduced())
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = sum(np.prod(a.shape) for a in jax.tree.leaves(params))
    # heads are stacked per owner: one owner's head counts once
    head = sum(np.prod(a.shape[1:]) for a in jax.tree.leaves(
        params["heads"]))
    trunk = sum(np.prod(a.shape) for a in jax.tree.leaves(params["trunk"]))
    assert n == 2 * head + trunk
    r = c.reduced()
    # param_count leaves out the RMSNorm gains (two a layer, and the last)
    assert head + trunk - r.param_count() == (2 * r.n_layers + 1) * r.d_model


def _moe_params(key, d, moe_cfg):
    return moe_mod.moe_init(key, d, moe_cfg, "swiglu")


def _share(params, cfg, r):
    """Share ``r``'s parameters: its block of the uncut layer's experts."""
    H = cfg.n_held
    out = dict(params)
    for k in ("w_in", "w_gate", "w_out"):
        out[k] = params[k][r * H:(r + 1) * H]
    return out


@pytest.mark.parametrize("norm_topk, aux_kind", [(False, "seq"),
                                                 (True, "switch")])
def test_the_shares_of_all_experts_add_up_to_the_uncut_layer(norm_topk,
                                                             aux_kind):
    """8 shares of E/8 experts each, with the shared experts and the
    balance loss counted once, give the uncut layer's output and loss."""
    E, K, d = 16, 6, 32
    uncut = MoEConfig(n_experts=E, top_k=K, d_expert=24, n_shared=2,
                      d_shared=24, norm_topk=norm_topk, aux_kind=aux_kind,
                      n_held=E)
    params = _moe_params(jax.random.PRNGKey(0), d, uncut)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 40, d), jnp.float32)
    want, want_aux = moe_mod.moe_apply(params, x, uncut, "swiglu")
    shared = jax.vmap(lambda xb: moe_mod.mlp_mod.mlp_apply(
        params["shared"], xb, "swiglu"))(x)
    total, auxes = shared, []
    for r in range(8):
        cfg = dataclasses.replace(uncut, n_held=E // 8,
                                  held_first=r * E // 8)
        out, aux = moe_mod.moe_apply(_share(params, cfg, r), x, cfg,
                                     "swiglu")
        total = total + (out - shared)
        auxes.append(float(aux))
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert auxes == pytest.approx([float(want_aux)] * 8, rel=1e-6)


def test_the_share_layer_in_blocks_is_the_share_layer_whole(monkeypatch):
    """Blocks of tokens, each recomputed in the backward pass, give the
    whole layer's output, balance loss, counters and gradients."""
    E, K, d = 16, 6, 32
    cfg = MoEConfig(n_experts=E, top_k=K, d_expert=24, n_shared=1,
                    d_shared=24, norm_topk=False, aux_kind="seq", n_held=4,
                    held_first=4)
    params = _moe_params(jax.random.PRNGKey(4), d, cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, d), jnp.float32)

    def run():
        def f(p, x):
            out, aux, stats = moe_mod.moe_apply(p, x, cfg, "swiglu",
                                                with_stats=True)
            return jnp.sum(out * out) + aux, (out, aux, stats)
        return jax.grad(f, argnums=(0, 1), has_aux=True)(params, x)

    whole = run()
    monkeypatch.setattr(moe_mod, "SHARE_BLOCK_TOKENS", 32)
    blocks = run()
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(blocks)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)


def test_share_counters_count_routed_choices_and_buffer_rows():
    E, K, d, H = 16, 6, 32, 4
    cfg = MoEConfig(n_experts=E, top_k=K, d_expert=24, n_shared=0,
                    norm_topk=False, aux_kind="seq", n_held=H, held_first=4)
    params = _moe_params(jax.random.PRNGKey(2), d, cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 300, d), jnp.float32)
    _, _, stats = moe_mod.moe_apply(params, x, cfg, "swiglu",
                                    with_stats=True)
    _, _, top_e = moe_mod.route(x.reshape(-1, d), params["router"], cfg)
    sizes = np.bincount(np.asarray(top_e).ravel(), minlength=E)[4:8]
    assert float(stats["moe_routed"]) == sizes.sum()
    assert float(stats["moe_peak"]) == sizes.max()
    assert float(stats["moe_mean"]) == pytest.approx(sizes.sum() / H)
    # the buffer holds a row for every choice that could land here
    assert float(stats["moe_rows"]) == 2 * 300 * min(K, H)
    assert float(stats["moe_rows"]) >= float(stats["moe_routed"])


def test_dense_head_and_moe_trunk_split_at_the_boundary():
    cfg = get_config("deepseek-v2-lite", reduced=True)
    model = SplitModel(cfg)
    assert (model.head_ffn, model.trunk_ffn) == ("dense", "moe")
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    head_ffn = params["heads"]["blocks"]["units"]["b0"]["ffn"]
    trunk_ffn = params["trunk"]["blocks"]["units"]["b0"]["ffn"]
    assert "router" not in head_ffn
    assert head_ffn["w_in"]["w"].shape[-1] == cfg.d_ff
    assert trunk_ffn["router"]["w"].shape[-1] == cfg.moe.n_experts
    with pytest.raises(ValueError, match="dense/MoE boundary"):
        SplitModel(cfg.with_split(cut_layer=2).replace(n_layers=3))


def test_the_serving_engine_refuses_latent_attention():
    from repro.federation.registry import build_adapter
    cfg = get_config("deepseek-v2-lite", reduced=True)
    adapter = build_adapter(cfg)
    with pytest.raises(ValueError, match="latent KV cache"):
        adapter.make_engine(None)
