"""Model substrate: every family's train/prefill/decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import lm, ssm, xlstm
from repro.models.config import ModelConfig

BASE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
            vocab=97, dtype=jnp.float32, remat="none")

FAMILIES = {
    "dense": ModelConfig(name="dense", family="dense", qk_norm=True, qkv_bias=True, **BASE),
    "parallel": ModelConfig(name="par", family="dense", parallel_block=True,
                            norm="layernorm", **BASE),
    "moe": ModelConfig(name="moe", family="moe", n_experts=4, top_k=2,
                       capacity_factor=8.0, **{**BASE, "d_ff": 96}),
    "hybrid": ModelConfig(name="hyb", family="hybrid", ssm_state=4,
                          sliding_window=6, **BASE),
    "xlstm": ModelConfig(name="xl", family="xlstm", slstm_every=2,
                         **{**BASE, "d_ff": 0, "n_kv_heads": 4, "n_layers": 4}),
    "vlm": ModelConfig(name="vlm", family="vlm", n_patches=4, act="gelu",
                       emb_scale=True, tie_embeddings=True, **{**BASE, "n_kv_heads": 1}),
    "audio": ModelConfig(name="aud", family="audio", n_codebooks=4,
                         norm="layernorm", act="gelu", pos_emb="sinusoidal",
                         **{**BASE, "vocab": 33, "n_kv_heads": 4}),
}


def _tokens(cfg, B, S, key):
    shape = (B, S, cfg.n_codebooks) if cfg.n_codebooks else (B, S)
    return jax.random.randint(key, shape, 0, cfg.vocab)


@pytest.mark.parametrize("fam", list(FAMILIES))
def test_forward_loss_and_grads(fam):
    cfg = FAMILIES[fam]
    key = jax.random.PRNGKey(0)
    params, buffers = lm.init(key, cfg)
    batch = {"tokens": _tokens(cfg, 2, 8, key)}
    if cfg.family == "vlm":
        batch["patch_emb"] = jnp.ones((2, cfg.n_patches, cfg.d_model))
    loss, metrics = lm.next_token_loss(params, buffers, cfg, batch)
    assert np.isfinite(float(loss))
    assert float(loss) < 2 * np.log(cfg.vocab) + 3
    g = jax.grad(lambda p: lm.next_token_loss(p, buffers, cfg, batch)[0])(params)
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))


@pytest.mark.parametrize("fam", list(FAMILIES))
def test_prefill_decode_match_forward(fam):
    cfg = FAMILIES[fam]
    key = jax.random.PRNGKey(1)
    params, buffers = lm.init(key, cfg)
    B, S = 2, 8
    toks = _tokens(cfg, B, S + 1, key)
    batch = {"tokens": toks}
    if cfg.family == "vlm":  # decode path without image for the cache test
        pass
    logits, _ = lm.forward(params, buffers, cfg, batch)
    cache = lm.init_cache(cfg, B, 16)
    lgp, cache = lm.prefill(params, buffers, cfg, toks[:, :S], cache)
    np.testing.assert_allclose(
        np.asarray(lgp), np.asarray(logits[:, S - 1]), rtol=1e-3, atol=1e-3
    )
    nxt = toks[:, S]
    lgd, cache = lm.decode_step(
        params, buffers, cfg, nxt, jnp.full((B,), S, jnp.int32), cache
    )
    np.testing.assert_allclose(
        np.asarray(lgd), np.asarray(logits[:, S]), rtol=1e-3, atol=2e-3
    )


def test_scan_equals_unrolled():
    cfg = FAMILIES["dense"]
    key = jax.random.PRNGKey(2)
    params, buffers = lm.init(key, cfg)
    batch = {"tokens": _tokens(cfg, 2, 8, key)}
    l1, _ = lm.next_token_loss(params, buffers, cfg, batch)
    import dataclasses

    cfg2 = dataclasses.replace(cfg, scan_layers=False)
    l2, _ = lm.next_token_loss(params, buffers, cfg2, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_remat_does_not_change_loss():
    import dataclasses

    cfg = dataclasses.replace(FAMILIES["dense"], remat="full")
    key = jax.random.PRNGKey(3)
    params, buffers = lm.init(key, cfg)
    batch = {"tokens": _tokens(cfg, 2, 8, key)}
    l1, _ = lm.next_token_loss(params, buffers, cfg, batch)
    l2, _ = lm.next_token_loss(
        params, buffers, dataclasses.replace(cfg, remat="none"), batch
    )
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    g = jax.grad(lambda p: lm.next_token_loss(p, buffers, cfg, batch)[0])(params)
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))


def test_sliding_window_limits_attention():
    """With window w, logits at position t must not depend on tokens < t-w."""
    cfg = ModelConfig(name="swa", family="dense", sliding_window=3, **{
        k: v for k, v in BASE.items()})
    key = jax.random.PRNGKey(4)
    params, buffers = lm.init(key, cfg)
    t1 = _tokens(cfg, 1, 10, key)
    t2 = t1.at[0, 0].set((t1[0, 0] + 1) % cfg.vocab)  # perturb far past
    l1, _ = lm.forward(params, buffers, cfg, {"tokens": t1})
    l2, _ = lm.forward(params, buffers, cfg, {"tokens": t2})
    # receptive field stacks: 2 layers x (window-1) = 4 positions back, so
    # positions >= 5 can't see token 0 through any path
    np.testing.assert_allclose(
        np.asarray(l1[0, 5:]), np.asarray(l2[0, 5:]), rtol=1e-5, atol=1e-5
    )
    assert not np.allclose(np.asarray(l1[0, 0]), np.asarray(l2[0, 0]))


def test_ssm_chunk_invariance():
    cfg = FAMILIES["hybrid"]
    p = ssm.init_ssm(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, cfg.d_model))
    y1 = ssm.ssm_train(p, cfg, x, chunk=3)
    y2 = ssm.ssm_train(p, cfg, x, chunk=12)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-5)


def test_mlstm_chunk_invariance():
    cfg = FAMILIES["xlstm"]
    p = xlstm.init_mlstm(jax.random.PRNGKey(7), cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, cfg.d_model)) * 0.5
    y1, s1 = xlstm.mlstm_train(p, cfg, x, chunk=4)
    y2, s2 = xlstm.mlstm_train(p, cfg, x, chunk=12)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1[0]), np.asarray(s2[0]), rtol=2e-3, atol=2e-4)


def test_vlm_patches_shift_logits():
    cfg = FAMILIES["vlm"]
    key = jax.random.PRNGKey(9)
    params, buffers = lm.init(key, cfg)
    toks = _tokens(cfg, 1, 6, key)
    pe1 = jnp.zeros((1, cfg.n_patches, cfg.d_model))
    pe2 = jnp.ones((1, cfg.n_patches, cfg.d_model))
    l1, _ = lm.forward(params, buffers, cfg, {"tokens": toks, "patch_emb": pe1})
    l2, _ = lm.forward(params, buffers, cfg, {"tokens": toks, "patch_emb": pe2})
    assert l1.shape == (1, 6, cfg.vocab)  # logits only for text positions
    assert not np.allclose(np.asarray(l1), np.asarray(l2))


# --- DLRM-DCNv2 with multi-hot bags -------------------------------------------

def _dcnv2_case(use_kernel):
    from repro.models import dlrm
    from repro.models.dlrm import DLRMConfig

    cfg = DLRMConfig(
        vocab_sizes=(900, 7, 5000), emb_dim=16, bottom_mlp=(32, 16),
        top_mlp=(32, 8, 1), emb_method="cce", emb_param_cap=512, emb_c=4,
        bag_sizes=(3, 1, 2), interaction="dcnv2", cross_layers=2, cross_rank=8,
        emb_use_kernel=use_kernel)
    params, buffers = dlrm.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    B = 20
    sparse = np.concatenate([rng.integers(0, v, (B, n)) for v, n in
                             zip(cfg.vocab_sizes, cfg.bag_sizes)], axis=1)
    batch = {"dense": jnp.asarray(rng.normal(size=(B, 13)), jnp.float32),
             "sparse": jnp.asarray(sparse, jnp.int32),
             "label": jnp.asarray(rng.integers(0, 2, B), jnp.float32)}
    return cfg, params, buffers, batch


def _dcnv2_plain_loss(per_feature, dense_params, buffers, cfg, batch):
    """DCNv2 written out: each feature's embedding the sum of its bag's
    single-id lookups, x0 = [bottom MLP out, embeddings], low-rank cross
    layers, top MLP, BCE."""
    coll = cfg.collection
    x = batch["dense"]
    for layer in dense_params["bottom"]:
        x = jax.nn.relu(x @ layer["w"] + layer["b"])
    embs, off = [], 0
    for f, bag in enumerate(cfg.bag_sizes):
        t, b = coll.tables[f], coll.unstack_buffers(buffers["emb"])[f]
        embs.append(sum(t.lookup(per_feature[f], b, batch["sparse"][:, off + j])
                        for j in range(bag)))
        off += bag
    x0 = jnp.concatenate([x] + embs, axis=-1)
    x = x0
    for layer in dense_params["cross"]:
        x = x0 * ((x @ layer["v"]) @ layer["w"] + layer["b"]) + x
    for i, layer in enumerate(dense_params["top"]):
        x = x @ layer["w"] + layer["b"]
        if i < len(dense_params["top"]) - 1:
            x = jax.nn.relu(x)
    z, y = x[:, 0], batch["label"]
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


@pytest.mark.parametrize("use_kernel", [True, False])
def test_dcnv2_loss_and_grads_match_plain_reference(use_kernel):
    """The program's DCNv2 over sum-pooled bags of mixed lengths (1 among
    them), through the fused lookup (interpret mode) or the jnp path,
    against the model written out with single-id lookups."""
    from repro.models import dlrm

    cfg, params, buffers, batch = _dcnv2_case(use_kernel)
    coll = cfg.collection
    assert [g.runs for g in coll.groups] == [((4, 3), (4, 1), (4, 2))]

    def prog(p):
        return dlrm.bce_loss(p, buffers, cfg, batch)

    def plain(p):
        dense = {k: v for k, v in p.items() if k != "emb"}
        return _dcnv2_plain_loss(coll.unstack_params(p["emb"]), dense, buffers, cfg, batch)

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(prog))(params)
        lr, gr = jax.jit(jax.value_and_grad(plain))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)
    assert float(jnp.abs(gp["cross"][0]["v"]).max()) > 0


def test_dot_interaction_keeps_its_params_and_default():
    """The default stays the dot interaction, with no cross layers."""
    from repro.models import dlrm
    from repro.models.dlrm import DLRMConfig

    cfg = DLRMConfig(vocab_sizes=(50, 60), emb_dim=8, bottom_mlp=(16, 8), top_mlp=(8, 1))
    assert cfg.interaction == "dot" and cfg.bag_sizes == ()
    params, _ = dlrm.init(jax.random.PRNGKey(0), cfg)
    assert set(params) == {"bottom", "emb", "top"}
    assert params["top"][0]["w"].shape[0] == 8 + 3  # bottom out + 3 pairs
    with pytest.raises(ValueError, match="cross_layers"):
        DLRMConfig(vocab_sizes=(50,), interaction="dcnv2")
