"""Model-component tests: MoE dispatch vs. per-token oracle, chunked vs. full
attention, RoPE properties, norm invariants (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.attention import (
    GQAConfig,
    _sdpa,
    _sdpa_chunked_causal,
    apply_rope,
    causal_mask,
    gqa_apply,
    gqa_init,
)
from repro.models.common import norm_params
from repro.models.layers import layernorm, rmsnorm
from repro.models.moe import MoEConfig, moe_apply, moe_init, router_topk


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def _moe_oracle(p, cfg, x):
    """Per-token loop: route each token to its top-k experts, no capacity."""
    gates, ids, _ = router_topk(p, cfg, x)
    w = p["experts"]
    outs = []
    for t in range(x.shape[0]):
        acc = jnp.zeros_like(x[t])
        for j in range(cfg.top_k):
            e = int(ids[t, j])
            h = jax.nn.silu(x[t] @ w["w1"][e]) * (x[t] @ w["w3"][e])
            acc = acc + gates[t, j] * (h @ w["w2"][e])
        outs.append(acc)
    y = jnp.stack(outs)
    if cfg.n_shared:
        s = p["shared"]
        y = y + (jax.nn.silu(x @ s["w1"]["w"]) * (x @ s["w3"]["w"])) @ s["w2"]["w"]
    return y


@pytest.mark.parametrize("n_shared", [0, 1])
def test_moe_matches_per_token_oracle(n_shared):
    cfg = MoEConfig(d_model=32, n_experts=8, top_k=2, d_ff=48, n_shared=n_shared,
                    capacity_factor=8.0)  # dropless
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 32))
    got, aux = moe_apply(p, cfg, x)
    want = _moe_oracle(p, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert float(aux["dropped_frac"]) == 0.0


def test_moe_capacity_drops_are_bounded():
    cfg = MoEConfig(d_model=16, n_experts=4, top_k=2, d_ff=16,
                    capacity_factor=1.0, dropless_below=0)
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (512, 16))
    y, aux = moe_apply(p, cfg, x)
    assert 0.0 <= float(aux["dropped_frac"]) < 0.5
    assert bool(jnp.isfinite(y).all())


def test_moe_load_balance_loss_sane():
    cfg = MoEConfig(d_model=16, n_experts=8, top_k=2, d_ff=16, capacity_factor=4.0)
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 16))
    _, aux = moe_apply(p, cfg, x)
    # perfectly balanced -> 1.0; collapsed -> ~ E; random init lands low
    assert 0.9 < float(aux["load_balance_loss"]) < 4.0


def test_moe_grads_flow_through_router():
    cfg = MoEConfig(d_model=16, n_experts=4, top_k=2, d_ff=16, capacity_factor=4.0)
    p = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    g = jax.grad(lambda pp: moe_apply(pp, cfg, x)[0].sum())(p)
    assert float(jnp.abs(g["router"]["w"]).sum()) > 0
    assert float(jnp.abs(g["experts"]["w1"]).sum()) > 0


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def test_chunked_attention_matches_full():
    b, t, h, hkv, d = 2, 4096, 4, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (b, t, h, d))
    k = jax.random.normal(keys[1], (b, t, hkv, d))
    v = jax.random.normal(keys[2], (b, t, hkv, d))
    full = _sdpa(q, k, v, causal_mask(t), d**-0.5)
    chunked = _sdpa_chunked_causal(q, k, v, d**-0.5, chunk=512)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(full), rtol=2e-4, atol=2e-4)


def test_rope_relative_property():
    """RoPE: <q_m, k_n> depends only on (m - n)."""
    d = 32
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 1, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, d))

    def dot_at(m, n):
        qm = apply_rope(q, jnp.array([[m]]), 1e4)[0, 0, 0]
        kn = apply_rope(k, jnp.array([[n]]), 1e4)[0, 0, 0]
        return float(qm @ kn)

    assert dot_at(5, 3) == pytest.approx(dot_at(12, 10), rel=1e-5)
    assert dot_at(7, 7) == pytest.approx(dot_at(0, 0), rel=1e-5)
    assert abs(dot_at(5, 3) - dot_at(50, 3)) > 1e-6  # genuinely positional


def test_gqa_decode_incremental_equals_batch():
    cfg = GQAConfig(d_model=32, n_heads=4, n_kv_heads=2, d_head=8, qk_norm=True)
    p = gqa_init(jax.random.PRNGKey(0), cfg)
    b, t = 2, 6
    x = jax.random.normal(jax.random.PRNGKey(1), (b, t, 32))
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    full, _ = gqa_apply(p, cfg, x, pos, causal_mask(t))
    k_cache = jnp.zeros((b, t, 2, 8))
    v_cache = jnp.zeros((b, t, 2, 8))
    for i in range(t):
        mask = (jnp.arange(t) <= i)[None, None, None, None]
        out, (k_cache, v_cache) = gqa_apply(
            p, cfg, x[:, i : i + 1], pos[:, i : i + 1], mask,
            kv=(k_cache, v_cache), cache_index=i,
        )
        np.testing.assert_allclose(
            np.asarray(out[:, 0]), np.asarray(full[:, i]), rtol=2e-4, atol=2e-4
        )


# ---------------------------------------------------------------------------
# norms / losses (hypothesis)
# ---------------------------------------------------------------------------


@given(st.integers(1, 8), st.integers(2, 64))
@settings(max_examples=30, deadline=None)
def test_layernorm_normalises(b, d):
    x = jax.random.normal(jax.random.PRNGKey(b * d), (b, d)) * 10 + 3
    y = layernorm(x, norm_params(d))
    np.testing.assert_allclose(np.asarray(jnp.mean(y, -1)), 0.0, atol=1e-4)
    if d > 4:
        np.testing.assert_allclose(np.asarray(jnp.var(y, -1)), 1.0, atol=1e-2)


@given(st.integers(2, 64))
@settings(max_examples=30, deadline=None)
def test_rmsnorm_scale_invariant(d):
    x = jax.random.normal(jax.random.PRNGKey(d), (3, d))
    p = norm_params(d, bias=False)
    y1 = rmsnorm(x, p)
    y2 = rmsnorm(7.5 * x, p)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# named scopes of the served programs, as the compiled HLO's op_names carry them
# ---------------------------------------------------------------------------


def _compiled_op_names(arch: str) -> set[str]:
    import re

    from repro.launch.serve import build_model

    cfg, params, fn = build_model(arch, smoke=True)
    x = jax.ShapeDtypeStruct((2, cfg.img_res, cfg.img_res, cfg.in_channels), jnp.float32)
    return set(re.findall(r'op_name="([^"]*)"', fn.func.lower(params, x).compile().as_text()))


def test_vgg16_program_carries_layer_slot_exchange_and_head_scopes():
    """``run_plan`` scopes each slot's segment ``layer<i>/<slot>``, the
    messages ``layer<i>/exchange`` and the final concatenation ``merge``;
    ``vgg.head`` its dense layers ``head/fc1-3``."""
    from repro.configs import get
    from repro.core import plan_halp

    names = _compiled_op_names("vgg16")
    plan = plan_halp(get("vgg16").smoke_cfg.geom(), overlap_rows=4)
    for i, part in enumerate(plan.parts):
        for es in plan.es_names:
            if part.out[es]:
                assert any(f"/layer{i:02d}/{es}/" in n for n in names), (i, es)
    assert any("/exchange/" in n for n in names)
    for j in (1, 2, 3):
        assert any(f"/head/fc{j}/" in n for n in names), j


def test_vit_program_carries_block_scopes():
    names = _compiled_op_names("vit-l16")
    for scope in ("attn", "mlp", "patch_embed", "head"):
        assert any(f"/{scope}/" in n for n in names), scope


def test_swin_program_carries_stage_scopes():
    """Each stage's blocks under ``stage<i>``: ``window_attn``, ``shift``,
    ``mlp``, and ``merge`` between stages; ``patch_embed`` and ``head``."""
    names = _compiled_op_names("swin-b-384")
    for i in (0, 1):
        for scope in ("window_attn", "shift", "mlp"):
            assert any(f"/stage{i}/" in n and f"/{scope}/" in n for n in names), (i, scope)
    assert any("/stage0/merge/" in n for n in names)
    assert not any("/stage1/merge/" in n for n in names)  # the last stage merges nothing
    for scope in ("patch_embed", "head"):
        assert any(f"/{scope}/" in n for n in names), scope
