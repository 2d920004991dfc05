"""Numerical equivalence of the §Perf optimization paths against baselines:
causal-skip flash scheduling, shard_map expert parallelism, attention
parallelism modes (no-op on a 1×1 mesh)."""

import dataclasses

import numpy as np
import pytest
jax = pytest.importorskip("jax")  # jax-native module: skip wholesale without jax
import jax.numpy as jnp

from repro import sharding
from repro.configs import get_config
from repro.kernels import ops, ref
from repro.launch.mesh import make_mesh
from repro.models import init_params, loss_fn
from repro.models import moe as moe_mod


def test_causal_skip_matches_naive():
    rng = np.random.default_rng(0)
    B, S, H, KV, hd = 2, 96, 6, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    o_ref = ref.attention_naive(q, k, v, causal=True)
    o_skip, lse_s = ref.flash_fwd_chunked(q, k, v, causal=True, q_chunk=32,
                                          kv_chunk=32, causal_skip=True)
    o_full, lse_f = ref.flash_fwd_chunked(q, k, v, causal=True, q_chunk=32,
                                          kv_chunk=32)
    np.testing.assert_allclose(np.asarray(o_skip), np.asarray(o_ref),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse_s), np.asarray(lse_f),
                               atol=1e-6)


def test_causal_skip_grad_path():
    """custom_vjp with causal_skip forward: backward matches naive grads
    (lse is identical, so the standard flash backward applies)."""
    rng = np.random.default_rng(1)
    B, S, H, KV, hd = 1, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, KV, hd)), jnp.float32)

    def loss_skip(q, k, v):
        o = ops.flash_attention(q, k, v, causal=True, impl="chunked",
                                q_chunk=16, kv_chunk=16, causal_skip=True)
        return jnp.sum(jnp.cos(o))

    def loss_naive(q, k, v):
        return jnp.sum(jnp.cos(ref.attention_naive(q, k, v, causal=True)))

    g1 = jax.grad(loss_skip, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


def test_ep_shard_map_matches_plain():
    """shard_map expert parallelism on a (1,1) mesh == plain path exactly
    (values and grads); E_local == E so drop semantics are identical."""
    cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b", smoke=True),
                              dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(2))
    p0 = jax.tree.map(lambda a: a[0], params["body"]["0"]["ffn"])
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 8, cfg.d_model)), jnp.float32) * 0.1

    y_plain, aux_plain = moe_mod.moe_apply(p0, x, cfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rules = dataclasses.replace(sharding.single_pod_rules(),
                                ep_shard_map=True)
    with sharding.mesh_context(mesh, rules):
        y_sm, aux_sm = moe_mod.moe_apply(p0, x, cfg)
        g_sm = jax.grad(lambda p: jnp.sum(
            jnp.sin(moe_mod.moe_apply(p, x, cfg)[0])))(p0)
    g_plain = jax.grad(lambda p: jnp.sum(
        jnp.sin(moe_mod.moe_apply(p, x, cfg)[0])))(p0)

    np.testing.assert_array_equal(np.asarray(y_plain), np.asarray(y_sm))
    assert float(aux_plain) == float(aux_sm)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_sm)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_full_model_loss_invariant_under_mesh_flags():
    """End-to-end: loss on a trivial mesh with all perf flags on equals the
    no-mesh loss (constraints are layout-only, never semantic)."""
    cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b", smoke=True),
                              dtype="float32", attn_causal_skip=True)
    params = init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16))),
             "targets": jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)))}
    loss0, _ = loss_fn(params, cfg, batch)
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = dataclasses.replace(sharding.single_pod_rules(fsdp=True),
                                attn_mode="auto", ep_shard_map=True)
    with sharding.mesh_context(mesh, rules):
        loss1, _ = loss_fn(params, cfg, batch)
    assert float(loss0) == pytest.approx(float(loss1), rel=1e-6)
