"""Kernel micro-bench: us/call in interpret mode (indicative; real numbers
need a TPU — interpret mode executes the kernel body with XLA-CPU ops)."""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def run(verbose=True):
    R = np.random.default_rng(0)
    rows = []

    q = jnp.asarray(R.standard_normal((1, 4, 256, 64)), jnp.float32)
    kv = jnp.asarray(R.standard_normal((1, 2, 256, 64)), jnp.float32)
    rows.append(("flash_attention_256", _time(
        lambda: ops.flash_attention(q, kv, kv, block_q=128, block_k=128))))

    qd = jnp.asarray(R.standard_normal((4, 8, 64)), jnp.float32)
    kc = jnp.asarray(R.standard_normal((4, 512, 2, 64)), jnp.float32)
    lens = jnp.asarray([512, 300, 128, 1], jnp.int32)
    rows.append(("decode_attention_512", _time(
        lambda: ops.decode_attention(qd, kc, kc, lens))))

    from repro.core.forest import CompactForest, train_forest
    X = R.standard_normal((512, 16)).astype(np.float32)
    y = R.integers(0, 4, 512)
    f = CompactForest.from_dense(train_forest(X, y, n_trees=16, max_depth=6))
    fa = (jnp.asarray(X), jnp.asarray(f.nodes), jnp.asarray(f.leaf))
    rows.append(("forest_infer_512x16", _time(
        lambda: ops.forest_infer(*fa, f.widths))))

    v = jnp.asarray(R.standard_normal((1024, 128)), jnp.float32)
    m = jnp.asarray(R.random((1024, 128)) < 0.5)
    rows.append(("flow_stats_1024", _time(lambda: ops.flow_stats(v, m))))

    x = jnp.asarray(R.standard_normal((1, 256, 2, 32)) * 0.3, jnp.float32)
    dt = jnp.asarray(np.abs(R.standard_normal((1, 256, 2))) * 0.1 + 0.01,
                     jnp.float32)
    A = jnp.asarray(-np.abs(R.standard_normal(2)) - 0.1, jnp.float32)
    Bm = jnp.asarray(R.standard_normal((1, 256, 8)) * 0.3, jnp.float32)
    rows.append(("mamba_scan_256", _time(
        lambda: ops.mamba_scan(x, dt, A, Bm, Bm, chunk=64))))

    if verbose:
        for name, us in rows:
            print(f"{name},{us:.1f},interpret-mode")
    return rows


if __name__ == "__main__":
    run()
