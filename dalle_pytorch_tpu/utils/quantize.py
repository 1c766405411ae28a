"""Post-training weight-only int8 quantization for serving.

Autoregressive decode reads every transformer kernel from HBM once per
generated token — at the flagship config that is ~0.4 GB/token in bf16 and
is the dominant cost of single-chip generation (the reference has no
quantized serving path at all; its sampling re-runs full forwards in fp16
at best, dalle_pytorch.py:481-493). Converting the Dense kernels (per-
output-channel symmetric scales) and the token-embedding tables (per-row
scales) to int8 halves those bytes; activations, norms, biases and every
other parameter stay in full precision, and the matvecs/gathers widen
int8 -> bf16 in registers (see ops/layers.py:QuantDense / QuantEmbed).

``quantize_dalle`` maps a trained DALLE + params to its ``serve_quant``
twin: the target parameter tree comes from ``jax.eval_shape`` on the quant
model's init (no compute), and each leaf is either copied from the source
tree or quantized from the matching kernel. flax auto-names swap
``Dense_i`` -> ``QuantDense_i`` inside feed-forward blocks; explicitly
named projections (to_qkv / to_out / to_logits) keep their paths.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import traverse_util


def quantize_kernel(w: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(in, out) float kernel -> (int8 kernel, (out,) f32 scale), symmetric
    per-output-channel: q = round(w / s), s = max|w_col| / 127."""
    w = np.asarray(w, np.float32)
    amax = np.max(np.abs(w), axis=0)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return q, scale


def quantize_embedding(w: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(vocab, dim) float table -> (int8 table, (vocab,) f32 scale),
    symmetric per-row (each gathered row dequantizes independently)."""
    w = np.asarray(w, np.float32)
    amax = np.max(np.abs(w), axis=1)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(w / scale[:, None]), -127, 127).astype(np.int8)
    return q, scale


def _src_path(path: Tuple[str, ...]) -> Tuple[str, ...]:
    """Target (quant) tree path -> source tree path: un-rename the flax
    auto-named QuantDense_i submodules; explicit names are unchanged."""
    return tuple(
        p.replace("QuantDense_", "Dense_") if p.startswith("QuantDense_") else p
        for p in path
    )


def quantize_params(dalle_quant, params, example_text, example_image) -> Dict[str, Any]:
    """Build the quantized parameter tree for ``dalle_quant``
    (a DALLE with serve_quant=True) from trained ``params``."""
    target = jax.eval_shape(
        dalle_quant.init, jax.random.key(0), example_text, example_image
    )["params"]
    flat_t = traverse_util.flatten_dict(target)
    flat_s = traverse_util.flatten_dict(params)

    out: Dict[Tuple[str, ...], Any] = {}
    quant_cache: Dict[Tuple[str, ...], Tuple[np.ndarray, np.ndarray]] = {}

    def quantized(src_path: Tuple[str, ...], fn):
        if src_path not in quant_cache:
            quant_cache[src_path] = fn(np.asarray(flat_s[src_path]))
        return quant_cache[src_path]

    for path, spec in flat_t.items():
        src = _src_path(path)
        if path[-1] == "kernel_q":
            q, _ = quantized(src[:-1] + ("kernel",), quantize_kernel)
            assert q.shape == spec.shape, (path, q.shape, spec.shape)
            out[path] = jnp.asarray(q)
        elif path[-1] == "embedding_q":
            q, _ = quantized(src[:-1] + ("embedding",), quantize_embedding)
            assert q.shape == spec.shape, (path, q.shape, spec.shape)
            out[path] = jnp.asarray(q)
        elif path[-1] == "scale" and (path[:-1] + ("kernel_q",)) in flat_t:
            _, s = quantized(src[:-1] + ("kernel",), quantize_kernel)
            out[path] = jnp.asarray(s)
        elif path[-1] == "scale" and (path[:-1] + ("embedding_q",)) in flat_t:
            _, s = quantized(src[:-1] + ("embedding",), quantize_embedding)
            out[path] = jnp.asarray(s)
        else:
            leaf = flat_s[src]
            assert leaf.shape == spec.shape, (path, leaf.shape, spec.shape)
            out[path] = leaf
    return traverse_util.unflatten_dict(out)


def quantize_dalle(dalle, params, batch_size: int = 1):
    """(dalle, trained params) -> (serve_quant dalle, int8 params) ready for
    ``models/sampling.py`` decode. Dense projections and the token-embedding
    tables are quantized; MoE expert banks and gMLP blocks pass through at
    full precision (pinned by tests/test_quantize.py)."""
    dalle_q = dalle.clone(serve_quant=True)
    text = jnp.zeros((batch_size, dalle.text_seq_len), jnp.int32)
    image = jnp.zeros((batch_size, dalle.image_seq_len), jnp.int32)
    return dalle_q, quantize_params(dalle_q, params, text, image)


def prepare_for_serving(dalle, params, int8: bool = False, batch_size: int = 1):
    """Standard serving transform: cast the model + f32 params to bf16
    (decode is HBM-bound on weight reads) and optionally quantize the Dense
    kernels to int8. The single home for the load sequence generate.py and
    chip_smoke.py share."""
    dalle = dalle.clone(dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        params,
    )
    if int8:
        dalle, params = quantize_dalle(dalle, params, batch_size=batch_size)
    return dalle, params
