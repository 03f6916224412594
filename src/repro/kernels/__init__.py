"""Pallas TPU kernels for the pipeline's compute hot-spots.

Kernels (each `<name>.py` is a `pl.pallas_call` + explicit BlockSpec tiling;
`ops.py` holds the jit'd wrappers; `ref.py` the pure-jnp oracles):

  flash_attention  fused online-softmax attention, GQA, causal block skip
  decode_attention single-token GQA decode over a dense KV cache
  tree_infer       real-node random-forest inference (model stage)
  feature_extract  masked segmented flow statistics (extraction stage)
  fused_pipeline   single-launch extract+infer over flow tiles (serving)
  mamba_scan       chunked SSD selective scan (SSM/hybrid archs, long ctx)
"""
from . import ops, ref

__all__ = ["ops", "ref"]
