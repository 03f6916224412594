"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.forest import (
    CompactForest, DenseForest, forest_apply_np, train_forest,
)
from repro.kernels import ops, ref

R = np.random.default_rng(0)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(R.standard_normal(shape) * scale, dtype)


@pytest.mark.parametrize("B,Hq,Hkv,Tq,Tk,D", [
    (1, 2, 2, 128, 128, 32),
    (2, 4, 2, 256, 256, 64),
    (1, 8, 1, 128, 256, 64),   # strong GQA + cross lengths
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, Hq, Hkv, Tq, Tk, D, causal, dtype):
    q = _arr((B, Hq, Tq, D), dtype)
    k = _arr((B, Hkv, Tk, D), dtype)
    v = _arr((B, Hkv, Tk, D), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol
    )


@pytest.mark.parametrize("B,Hq,Hkv,S,D,bs", [
    (2, 4, 2, 256, 64, 128),
    (3, 8, 8, 512, 32, 256),   # MHA
    (1, 16, 2, 300, 64, 128),  # padding path
])
def test_decode_attention_sweep(B, Hq, Hkv, S, D, bs):
    q = _arr((B, Hq, D))
    kc = _arr((B, S, Hkv, D))
    vc = _arr((B, S, Hkv, D))
    lens = jnp.asarray(R.integers(1, S + 1, B), jnp.int32)
    out = ops.decode_attention(q, kc, vc, lens, block_s=bs)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("n,F,K,T,depth", [
    (200, 6, 3, 7, 4),
    (512, 12, 28, 16, 6),
    (100, 4, 2, 3, 5),         # tree padding path (3 % 4 != 0)
])
def test_forest_infer_sweep(n, F, K, T, depth):
    X = R.standard_normal((n, F)).astype(np.float32)
    y = R.integers(0, K, n)
    f = train_forest(X, y, n_trees=T, max_depth=depth,
                     rng=np.random.default_rng(1))
    want = forest_apply_np(f, X)
    cf = CompactForest.from_dense(f)
    got = ops.forest_infer(
        jnp.asarray(X), jnp.asarray(cf.nodes), jnp.asarray(cf.leaf),
        cf.widths, block_n=128, block_t=4,
    )
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    got_ref = ref.forest_infer_ref(
        jnp.asarray(X), jnp.asarray(f.feature), jnp.asarray(f.threshold),
        jnp.asarray(f.leaf), f.depth,
    )
    np.testing.assert_allclose(np.asarray(got_ref), want, atol=1e-5)


@pytest.mark.parametrize("n,F,K,T,depth,block_t", [
    (64, 5, 4, 6, 3, 4),       # tree padding (6 % 4 != 0)
    (130, 9, 2, 8, 5, 8),      # flow padding (130 % 128 != 0)
    (256, 3, 7, 12, 6, 4),
])
def test_forest_infer_ref_vs_kernel_random(n, F, K, T, depth, block_t):
    """Direct ref-vs-Pallas parity on *random* dense forests: arbitrary
    feature ids, thresholds (incl. +inf pass-through slots, with real
    splits below them) and leaves — not just trainer-produced trees."""
    rng = np.random.default_rng(n + T)
    n_int, n_leaf = 2 ** depth - 1, 2 ** depth
    feature = rng.integers(0, F, (T, n_int)).astype(np.int32)
    threshold = rng.standard_normal((T, n_int)).astype(np.float32)
    threshold[rng.random((T, n_int)) < 0.15] = np.inf  # pass-through slots
    leaf = rng.random((T, n_leaf, K)).astype(np.float32)
    X = rng.standard_normal((n, F)).astype(np.float32)
    cf = CompactForest.from_dense(DenseForest(feature, threshold, leaf, depth, F))
    got = ops.forest_infer(
        jnp.asarray(X), jnp.asarray(cf.nodes), jnp.asarray(cf.leaf),
        cf.widths, block_n=128, block_t=block_t,
    )
    want = ref.forest_infer_ref(
        jnp.asarray(X), jnp.asarray(feature), jnp.asarray(threshold),
        jnp.asarray(leaf), depth,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("n,P", [(64, 32), (300, 96), (1000, 128)])
def test_flow_stats_sweep(n, P):
    v = _arr((n, P))
    m = jnp.asarray(R.random((n, P)) < 0.4)
    got = ops.flow_stats(v, m, block_n=128)
    want = ref.flow_stats_ref(v, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # empty-mask row
    m0 = jnp.zeros((n, P), bool)
    got0 = ops.flow_stats(v, m0, block_n=128)
    assert np.all(np.asarray(got0) == 0)


@pytest.mark.parametrize("B,T,H,P,S,chunk", [
    (1, 128, 2, 16, 8, 32),
    (2, 256, 4, 32, 16, 64),
    (1, 192, 1, 64, 4, 64),
])
def test_mamba_scan_sweep(B, T, H, P, S, chunk):
    x = _arr((B, T, H, P), scale=0.5)
    dt = jnp.abs(_arr((B, T, H), scale=0.1)) + 0.01
    A = -jnp.abs(_arr((H,), scale=1.0)) - 0.1
    Bm = _arr((B, T, S), scale=0.3)
    Cm = _arr((B, T, S), scale=0.3)
    got = ops.mamba_scan(x, dt, A, Bm, Cm, chunk=chunk)
    want = ref.mamba_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)


def test_chunked_ssd_matches_kernel_path():
    """The model-side chunked SSD equals the Pallas kernel recurrence."""
    from repro.models.ssm import chunked_ssd

    B, T, H, P, S = 2, 128, 2, 16, 8
    x = _arr((B, T, H, P), scale=0.5)
    dt = jnp.abs(_arr((B, T, H), scale=0.1)) + 0.01
    A = -jnp.abs(_arr((H,), scale=1.0)) - 0.1
    Bm = _arr((B, T, S), scale=0.3)
    Cm = _arr((B, T, S), scale=0.3)
    y_model, _ = chunked_ssd(x, dt * A, dt, Bm[:, :, None], Cm[:, :, None], chunk=32)
    y_ref = ref.mamba_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_ref), atol=3e-4)


@pytest.mark.parametrize("backend,interpret,want", [
    ("cpu", None, True), ("cpu", True, True), ("cpu", False, False),
    ("tpu", None, False), ("tpu", False, False), ("gpu", False, False),
    ("tpu", True, RuntimeError), ("gpu", True, RuntimeError),
    ("gpu", None, RuntimeError),
])
def test_resolve_interpret_never_interprets_off_cpu(
        monkeypatch, backend, interpret, want):
    """Interpret mode runs only on the CPU backend: a served kernel on an
    accelerator compiles with Mosaic or raises, never interprets."""
    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    if want is RuntimeError:
        with pytest.raises(RuntimeError):
            ops.resolve_interpret(interpret)
    else:
        assert ops.resolve_interpret(interpret) is want
