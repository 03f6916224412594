"""Public jit'd wrappers for the Pallas kernels.

Each wrapper pads inputs to block multiples, dispatches the kernel, and
slices the result. `interpret` defaults to auto (`resolve_interpret`):
Pallas interpret mode on the CPU backend, compiled Mosaic on a TPU; any
other backend is an error, never a silent interpret. The pure-jnp oracles
(`use_kernel=False`) are the ref implementations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .decode_attention import decode_attention_kernel_call
from .feature_extract import flow_stats_kernel_call
from .flash_attention import flash_attention_kernel_call
from .mamba_scan import mamba_scan_kernel_call
from .tree_infer import forest_infer_kernel_call

__all__ = [
    "resolve_interpret",
    "flash_attention",
    "decode_attention",
    "forest_infer",
    "flow_stats",
    "mamba_scan",
]


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode on this backend.

    ``None`` picks interpret mode on the CPU backend and Mosaic on a TPU.
    Interpret mode on any backend but the CPU raises, and so does ``None``
    on a backend that is neither: a served kernel never falls back to the
    interpreter on an accelerator. ``False`` is always honoured, so a
    kernel can be compiled for a described TPU from a CPU process.
    """
    backend = jax.default_backend()
    if interpret is None:
        if backend not in ("cpu", "tpu"):
            raise RuntimeError(
                f"no Pallas kernel path for backend {backend!r}: kernels "
                "compile with Mosaic on a TPU and interpret only on the CPU")
        interpret = backend == "cpu"
    if interpret and backend != "cpu":
        raise RuntimeError(
            f"Pallas interpret mode requested on backend {backend!r}; "
            "interpret mode runs only on the CPU backend")
    return interpret


def _pad_to(x: jax.Array, axis: int, mult: int, value=0.0):
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x, n
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads, constant_values=value), n


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def flash_attention(
    q, k, v, *, causal=True, scale=None, block_q=128, block_k=128, interpret=None
):
    interpret = resolve_interpret(interpret)
    Tq, Tk = q.shape[2], k.shape[2]
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    if Tq % bq or Tk % bk:
        # pad sequence dims; padded keys are masked out by causality only if
        # they sit past the end — safest to pad both to block multiples and
        # mask via an explicit causal offset, so restrict padding to q here
        q_p, tq0 = _pad_to(q, 2, bq)
        out = flash_attention_kernel_call(
            q_p, k, v, causal=causal, scale=scale,
            block_q=bq, block_k=bk, interpret=interpret,
        )
        return out[:, :, :tq0]
    return flash_attention_kernel_call(
        q, k, v, causal=causal, scale=scale,
        block_q=bq, block_k=bk, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("scale", "block_s", "interpret"))
def decode_attention(q, k_cache, v_cache, lengths, *, scale=None, block_s=256,
                     interpret=None):
    interpret = resolve_interpret(interpret)
    S = k_cache.shape[1]
    bs = min(block_s, S)
    k_p, _ = _pad_to(k_cache, 1, bs)
    v_p, _ = _pad_to(v_cache, 1, bs)
    # padded cache positions are masked by `lengths`
    return decode_attention_kernel_call(
        q, k_p, v_p, lengths, scale=scale, block_s=bs, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("widths", "block_n", "block_t", "interpret"))
def forest_infer(x, nodes, leaf, widths, *, block_n=256, block_t=8,
                 interpret=None):
    """Mean leaf votes of a `CompactForest` (``nodes``, ``leaf``,
    ``widths``) for each row of `x`; flow/tree padding lives in the kernel
    call."""
    interpret = resolve_interpret(interpret)
    return forest_infer_kernel_call(
        x, nodes, leaf, widths,
        block_n=block_n, block_t=block_t, interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def flow_stats(values, mask, *, block_n=512, interpret=None):
    interpret = resolve_interpret(interpret)
    return flow_stats_kernel_call(
        values, mask, block_n=block_n, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_scan(x, dt, A, Bm, Cm, *, chunk=128, interpret=None):
    interpret = resolve_interpret(interpret)
    T = x.shape[1]
    c = min(chunk, T)
    if T % c:
        x, t0 = _pad_to(x, 1, c)
        dt, _ = _pad_to(dt, 1, c)
        Bm, _ = _pad_to(Bm, 1, c)
        Cm, _ = _pad_to(Cm, 1, c)
        out = mamba_scan_kernel_call(x, dt, A, Bm, Cm, chunk=c, interpret=interpret)
        return out[:, :t0]
    return mamba_scan_kernel_call(x, dt, A, Bm, Cm, chunk=c, interpret=interpret)
