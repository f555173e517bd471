"""Structural invariants of the model substrate."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models import xlstm as xlstm_lib


# ---------------------------------------------------------------------------
# RoPE: attention logits depend only on relative position
# ---------------------------------------------------------------------------

def test_rope_relative_position():
    hd = 32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 1, 1, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, hd))

    def logit(qpos, kpos):
        qr = L.apply_rope(q, jnp.array([[qpos]]), 10_000.0)
        kr = L.apply_rope(k, jnp.array([[kpos]]), 10_000.0)
        return float(jnp.sum(qr[0, 0, 0] * kr[0, 0, 0]))

    assert abs(logit(7, 3) - logit(107, 103)) < 1e-3
    assert abs(logit(7, 3) - logit(9, 3)) > 1e-5   # but not absolute


def test_rms_norm_scale_invariance():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    s = jnp.zeros((16,))
    a = L.rms_norm(x, s)
    b = L.rms_norm(x * 100.0, s)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ---------------------------------------------------------------------------
# MoE: capacity accounting
# ---------------------------------------------------------------------------

def test_moe_routing_weight_conservation():
    """Each surviving token's routing weights sum to <= 1 (== 1 when no
    assignment of that token was capacity-dropped)."""
    key = jax.random.PRNGKey(0)
    d, e, ff = 16, 4, 32
    params = moe_lib.moe_init(key, d, ff, e, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 8, d))
    out, aux = moe_lib.moe_apply(params, x, top_k=2, capacity_factor=2.0)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) >= 0.99   # Switch aux loss >= 1 at balance


def test_moe_capacity_drops_overflow():
    """With capacity_factor ~ 0, everything drops -> output ~ 0."""
    key = jax.random.PRNGKey(0)
    d, e, ff = 8, 2, 16
    params = moe_lib.moe_init(key, d, ff, e, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 16, d))
    out_full, _ = moe_lib.moe_apply(params, x, top_k=1,
                                    capacity_factor=4.0)
    # capacity 1 slot per expert: most tokens dropped
    out_tiny, _ = moe_lib.moe_apply(params, x, top_k=1,
                                    capacity_factor=1.0 / 16.0)
    assert float(jnp.sum(jnp.abs(out_tiny))) \
        < float(jnp.sum(jnp.abs(out_full)))


# ---------------------------------------------------------------------------
# Mamba: chunk-size invariance of the scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [(8, 16), (16, 64)])
def test_mamba_chunk_size_invariance(chunks):
    key = jax.random.PRNGKey(0)
    d = 16
    params = mamba_lib.mamba_init(key, d, expand=2, d_state=4, d_conv=4,
                                  dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 64, d))
    y1, st1 = mamba_lib.mamba_apply(params, x, chunk=chunks[0])
    y2, st2 = mamba_lib.mamba_apply(params, x, chunk=chunks[1])
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st1["ssm"]),
                               np.asarray(st2["ssm"]),
                               rtol=2e-4, atol=2e-4)


def test_mamba_decode_matches_apply():
    """Token-by-token decode == full-sequence scan."""
    key = jax.random.PRNGKey(0)
    d, s = 16, 12
    params = mamba_lib.mamba_init(key, d, expand=2, d_state=4, d_conv=4,
                                  dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, s, d))
    y_full, _ = mamba_lib.mamba_apply(params, x, chunk=s)
    st = mamba_lib.init_mamba_state(1, d, 2, 4, 4, jnp.float32)
    ys = []
    for i in range(s):
        y, st = mamba_lib.mamba_decode(params, x[:, i: i + 1], st)
        ys.append(y)
    y_dec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_full),
                               rtol=5e-4, atol=5e-4)


# ---------------------------------------------------------------------------
# mLSTM: chunkwise == decode recurrence
# ---------------------------------------------------------------------------

def test_mlstm_chunkwise_matches_decode():
    key = jax.random.PRNGKey(0)
    d, h, hd, s = 32, 2, 16, 8
    params = xlstm_lib.mlstm_init(key, d, h, hd, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, s, d))
    y_chunk, _ = xlstm_lib.mlstm_apply(params, x, chunk=s)
    st = xlstm_lib.init_mlstm_state(1, h, hd)
    ys = []
    for i in range(s):
        y, st = xlstm_lib.mlstm_decode(params, x[:, i: i + 1], st)
        ys.append(y)
    y_dec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_chunk),
                               rtol=2e-3, atol=2e-3)


def test_xlstm_head_norm_cancels_the_cell_normalizer():
    """The mLSTM read-out is divided by a per-(position, head) scalar;
    the head norm makes the block output independent of it."""
    key = jax.random.PRNGKey(0)
    y = jax.random.normal(key, (2, 5, 3, 16))
    c = jnp.exp(jax.random.normal(jax.random.fold_in(key, 1), (2, 5, 3)))
    scale = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (3, 16))
    np.testing.assert_allclose(
        np.asarray(xlstm_lib.head_norm(y * c[..., None], scale)),
        np.asarray(xlstm_lib.head_norm(y, scale)), rtol=1e-4, atol=1e-4)


def test_xlstm_gradient_does_not_grow_geometrically_with_depth():
    """Doubling the xLSTM stack (12 -> 24 blocks) at init must not
    multiply the gradient norm the way a geometric growth would: before
    the head norm it rose 17x here (and 2x per block at full width)."""
    import dataclasses

    from repro.configs import get_config, reduced
    from repro.models import transformer as tfm
    from repro.models.config import Stage

    base = reduced(get_config("xlstm-125m"))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                              base.vocab_size)

    def grad_norm(repeats):
        cfg = dataclasses.replace(
            base, n_layers=6 * repeats,
            stages=(Stage(base.stages[0].pattern, repeats),)).validate()
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        g = jax.grad(lambda p: tfm.train_loss(cfg, p, toks))(params)
        return float(jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                  for x in jax.tree.leaves(g))))

    assert grad_norm(4) < 4.0 * grad_norm(2)
