"""Per-arch smoke tests (reduced configs): one forward/train step on CPU,
output shapes + finiteness + grads; decode-vs-forward consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_reduced
from repro.models import (decode_step, encode, forward, init_decode_state,
                          init_params, loss_fn)
from repro.models.transformer import Impl

IMPL = Impl(attention="chunked", ssd="chunked", q_chunk=16, kv_chunk=16,
            remat=True)
B, S = 2, 32


def _batch(cfg, key):
    k1, k2 = jax.random.split(key)
    batch = {"tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size)}
    if cfg.vision_tokens:
        batch["vision_embeds"] = jnp.full(
            (B, cfg.vision_tokens, cfg.vision_dim), 0.1, jnp.float32)
        batch["labels"] = batch["labels"].at[:, :cfg.vision_tokens].set(-1)
    if cfg.enc_dec:
        batch["frames"] = jnp.full((B, cfg.enc_ctx, cfg.d_model), 0.1, jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_grad(arch):
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    logits, aux = forward(cfg, params, batch, impl=IMPL, dtype=jnp.float32)
    from repro.models.layers import padded_vocab
    assert logits.shape == (B, S, padded_vocab(cfg.vocab_size))
    assert np.isfinite(np.asarray(logits[..., :cfg.vocab_size])).all()
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, batch, impl=IMPL, dtype=jnp.float32),
        has_aux=True)(params)
    assert np.isfinite(float(loss))
    gn = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_runs(arch):
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    enc_out = (encode(cfg, params, batch["frames"], impl=IMPL)
               if cfg.enc_dec else None)
    st = init_decode_state(cfg, params, B, 64, dtype=jnp.float32, impl=IMPL,
                           enc_out=enc_out)
    tok = batch["tokens"][:, :1]
    for _ in range(3):
        lg, st = decode_step(cfg, params, st, tok, impl=IMPL, dtype=jnp.float32)
        tok = jnp.argmax(lg[:, -1:, :cfg.vocab_size], -1).astype(jnp.int32)
    assert np.isfinite(np.asarray(lg[..., :cfg.vocab_size])).all()


# The strongest correctness check: teacher-forced incremental decode must
# reproduce the full-sequence forward logits for every family with a cache.
@pytest.mark.parametrize("arch", ["llama3.2-1b", "mamba2-1.3b", "zamba2-2.7b",
                                  "whisper-tiny", "mixtral-8x7b",
                                  "llava-next-mistral-7b",
                                  "granite-4.0-h-small", "olmo-1b",
                                  "qwen3-14b"])
def test_decode_matches_forward(arch):
    cfg = get_reduced(arch)
    if cfg.moe:
        # capacity-based MoE drops depend on how many tokens route together;
        # loosen capacity so neither path drops and the functions must agree
        from repro.configs import replace
        cfg = replace(cfg, moe=replace(cfg.moe, capacity_factor=8.0))
    impl = Impl(attention="naive", ssd="chunked", remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    n = 12
    batch = _batch(cfg, jax.random.PRNGKey(1))
    tokens = batch["tokens"][:, :n]
    fwd_batch = dict(batch, tokens=tokens,
                     labels=batch["labels"][:, :n])
    if cfg.vision_tokens:
        # decode path has no vision prefix; compare pure-text
        fwd_batch.pop("vision_embeds")
    ref_logits, _ = forward(cfg, params, fwd_batch, impl=impl, dtype=jnp.float32)

    enc_out = (encode(cfg, params, batch["frames"].astype(jnp.float32), impl=impl)
               if cfg.enc_dec else None)
    st = init_decode_state(cfg, params, B, n + 4, dtype=jnp.float32, impl=impl,
                           enc_out=enc_out)
    outs = []
    for t in range(n):
        lg, st = decode_step(cfg, params, st, tokens[:, t:t + 1], impl=impl,
                             dtype=jnp.float32)
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(dec_logits, ref_logits, rtol=2e-4, atol=2e-4)


DECODER_ONLY = [a for a in ARCH_IDS
                if get_reduced(a).family in ("dense", "moe", "ssm", "vlm")
                or get_reduced(a).layer_types]


def _stacks(cfg):
    """The stacks of ``params["blocks"]`` a family has → their layer count."""
    L = cfg.num_layers
    if cfg.family == "ssm":
        return {"ln1": L, "mamba": L}
    stacks = {"ln1": L, "ln2": L, "ffn": L}
    if cfg.layer_types:
        n_attn = cfg.layer_types.count("attention")
        return dict(stacks, mamba=L - n_attn, attn=n_attn)
    return dict(stacks, attn=L)


@pytest.mark.parametrize("arch", DECODER_ONLY)
def test_layer_stack_keeps_each_familys_tree(arch):
    """Every decoder-only family's layers are one stack: a mixer's weights
    stacked over the layers of its kind, the norms and the feed-forward
    over all layers; an SSM has no feed-forward. The benchmark's reference
    weights are laid out so."""
    cfg = get_reduced(arch)
    blocks = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))["blocks"]
    want = _stacks(cfg)
    assert set(blocks) == set(want)
    for name, n in want.items():
        for leaf in jax.tree.leaves(blocks[name]):
            assert leaf.shape[0] == n, (name, leaf.shape)
    if cfg.ffn_kind:
        ffn = {"held": {"router", "gate", "up", "down", "shared"},
               "moe": {"router", "gate", "up", "down"},
               "mlp": {"up", "down", "gate"} if cfg.mlp_type == "glu"
               else {"up", "down"}}[cfg.ffn_kind]
        assert set(blocks["ffn"]) == ffn


@pytest.mark.parametrize("arch", DECODER_ONLY)
def test_param_count_is_what_init_makes(arch):
    cfg = get_reduced(arch)
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert cfg.param_count() == sum(x.size for x in jax.tree.leaves(params))


def test_vlm_vision_prefix_changes_output():
    cfg = get_reduced("llava-next-mistral-7b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    l1, _ = forward(cfg, params, batch, impl=IMPL, dtype=jnp.float32)
    batch2 = dict(batch)
    batch2["vision_embeds"] = batch["vision_embeds"] * 2.0
    l2, _ = forward(cfg, params, batch2, impl=IMPL, dtype=jnp.float32)
    assert not np.allclose(np.asarray(l1), np.asarray(l2))


def test_loss_masks_labels():
    cfg = get_reduced("llama3.2-1b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, jax.random.PRNGKey(1))
    l_all, _ = loss_fn(cfg, params, batch, impl=IMPL, dtype=jnp.float32)
    batch_masked = dict(batch, labels=batch["labels"].at[:, :].set(-1))
    l_masked, _ = loss_fn(cfg, params, batch_masked, impl=IMPL, dtype=jnp.float32)
    assert float(l_masked) == 0.0
    assert float(l_all) > 0.0
