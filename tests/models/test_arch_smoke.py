"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes and absence of NaNs (assignment requirement),
plus prefill→decode consistency against the full-sequence forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch, reduced
from repro.models import model

B, S, MAX = 2, 16, 32


def _batch(cfg, key, with_labels=True):
    kt, kv = jax.random.split(key)
    s = S
    batch = {}
    if cfg.frontend == "vision":
        nv = cfg.n_frontend_tokens
        batch["vision_embeds"] = jax.random.normal(
            kv, (B, nv, cfg.frontend_dim), jnp.float32)
        s = S - nv
    if cfg.family == "encdec":
        batch["enc_embeds"] = jax.random.normal(
            kv, (B, S, cfg.frontend_dim), jnp.float32)
    batch["tokens"] = jax.random.randint(kt, (B, s), 0, cfg.vocab)
    if with_labels:
        batch["labels"] = jnp.roll(batch["tokens"], -1, axis=1)
    return batch


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch, rng):
    cfg = reduced(get_arch(arch))
    params = model.init(rng, cfg, jnp.float32)
    batch = _batch(cfg, rng)

    loss, metrics = model.loss_fn(params, batch, cfg, remat=False)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: loss not finite"

    # one SGD step via value_and_grad: gradients exist and are finite
    g = jax.grad(lambda p: model.loss_fn(p, batch, cfg, remat=True)[0])(params)
    leaves = jax.tree_util.tree_leaves(g)
    assert leaves, "no gradients"
    for leaf in leaves:
        assert np.all(np.isfinite(np.asarray(leaf, np.float32))), \
            f"{arch}: non-finite grad"
    # loss decreases after a small step (sanity, not convergence): grads
    # are a descent direction, so *some* small lr must help — backtrack
    # instead of hardwiring one lr for every family's loss landscape
    # (lr=0.1 marginally overshoots for the reduced MoE router)
    loss2 = np.inf
    for lr in (0.1, 0.03, 0.01):
        p2 = jax.tree_util.tree_map(lambda p, gg: p - lr * gg, params, g)
        loss2, _ = model.loss_fn(p2, batch, cfg, remat=False)
        if float(loss2) < float(loss) + 1e-3:
            break
    assert float(loss2) < float(loss) + 1e-3, f"{arch}: step did not help"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch, rng):
    """decode_step must continue exactly where prefill left off: logits for
    position S must match the full-sequence forward at position S."""
    cfg = reduced(get_arch(arch))
    params = model.init(rng, cfg, jnp.float32)
    batch = _batch(cfg, rng, with_labels=False)

    logits_p, cache = model.prefill(params, batch, cfg, MAX)
    next_tok = jnp.argmax(logits_p, -1).astype(jnp.int32)
    logits_d, cache = model.decode_step(params, next_tok, cache, cfg)
    assert logits_d.shape == (B, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits_d, np.float32)))

    # oracle: full forward over tokens + [next_tok]
    batch2 = dict(batch)
    batch2["tokens"] = jnp.concatenate(
        [batch["tokens"], next_tok[:, None]], axis=1)
    x, _ = model.forward(params, batch2, cfg, remat=False)
    table = params["embed"]["tok"] if cfg.tie_embeddings else params["unembed"]
    ref = jnp.einsum("bd,vd->bv", x[:, -1], table) / cfg.logits_scaling
    np.testing.assert_allclose(np.asarray(logits_d, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_abstract_params_match_init(arch, rng):
    """eval_shape of init == abstract_params (dry-run parity)."""
    cfg = reduced(get_arch(arch))
    abstract = model.abstract(cfg, jnp.float32)
    shaped = jax.eval_shape(lambda k: model.init(k, cfg, jnp.float32), rng)
    ta = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), abstract)
    tb = jax.tree_util.tree_map(lambda a: (a.shape, str(a.dtype)), shaped)
    assert ta == tb


def test_param_counts_nominal():
    """Full-config parameter counts are in the architecture's nominal range."""
    expect = {
        "qwen3-moe-30b-a3b": (29e9, 32e9),
        "granite-8b": (7.5e9, 8.7e9),
        "starcoder2-7b": (6.8e9, 7.8e9),
        "starcoder2-3b": (2.8e9, 3.4e9),
        "granite-3-2b": (2.3e9, 2.9e9),
        "pixtral-12b": (11.5e9, 13e9),
        "zamba2-1.2b": (1.0e9, 1.4e9),
        "mamba2-780m": (0.72e9, 0.84e9),
        "granite-4.0-h-micro": (3.0e9, 3.4e9),
        "whisper-small": (0.2e9, 0.4e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_arch(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n:,} outside [{lo:,.0f}, {hi:,.0f}]"
    # MoE active ≈ 3B for the a3b models
    for arch in ("qwen3-moe-30b-a3b", "moonshot-v1-16b-a3b"):
        a = get_arch(arch).active_param_count()
        assert 2e9 <= a <= 6.5e9, f"{arch} active {a:,}"
