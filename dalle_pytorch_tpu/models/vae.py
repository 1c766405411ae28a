"""Discrete VAE, TPU-native.

Re-owns the reference's Gumbel-softmax discrete VAE
(dalle_pytorch.py:60-225) as a flax module with explicit PRNG keys and
NHWC layout (the TPU-friendly conv layout — channels last keeps the MXU's
128-lane dimension on channels):

- conv encoder: ``num_layers`` stride-2 4x4 convs + ReLU, optional ResBlocks,
  1x1 conv to ``num_tokens`` logit channels;
- Gumbel-softmax relaxation (``jax.random.gumbel`` noise, temperature ``temp``,
  optional straight-through) over the codebook — the one-hot x codebook
  contraction is a single (b·h·w, num_tokens) x (num_tokens, d) matmul;
- conv-transpose decoder back to pixels;
- loss = recon (MSE or smooth-L1, dalle_pytorch.py:134,211) +
  ``kl_div_loss_weight`` x KL(q || uniform) with the reference's batchmean
  reduction (dalle_pytorch.py:213-220).

The reference mutates module state for temperature annealing; here ``temp`` is
a plain argument to ``__call__`` so the train step stays a pure function.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

Dtype = Any


def gumbel_softmax(
    logits: jnp.ndarray,
    key: jax.Array,
    temperature: float,
    hard: bool = False,
    axis: int = -1,
) -> jnp.ndarray:
    """Sample a relaxed one-hot from ``logits`` along ``axis``.

    ``hard=True`` gives the straight-through estimator: a true one-hot in the
    forward pass, the soft sample's gradient in the backward pass
    (reference uses F.gumbel_softmax, dalle_pytorch.py:202).
    """
    gumbels = jax.random.gumbel(key, logits.shape, dtype=jnp.float32)
    y_soft = jax.nn.softmax((logits.astype(jnp.float32) + gumbels) / temperature, axis=axis)
    if not hard:
        return y_soft.astype(logits.dtype)
    index = jnp.argmax(y_soft, axis=axis)
    y_hard = jax.nn.one_hot(index, logits.shape[axis], axis=axis, dtype=y_soft.dtype)
    return (y_hard + y_soft - jax.lax.stop_gradient(y_soft)).astype(logits.dtype)


def smooth_l1_loss(pred: jnp.ndarray, target: jnp.ndarray, beta: float = 1.0) -> jnp.ndarray:
    """Huber / smooth-L1 with torch's default beta=1, mean reduction."""
    diff = jnp.abs(pred - target)
    loss = jnp.where(diff < beta, 0.5 * diff**2 / beta, diff - 0.5 * beta)
    return loss.mean()


def denormalize(images, normalization=((0.5,) * 3, (0.5,) * 3)):
    """Invert ``DiscreteVAE.norm`` for display/save: the decoder emits pixels
    in normalized space (trained against ``norm(img)``), so saving them raw
    crushes the lower half of the range to black. x*std + mean, clipped to
    [0, 1]. The reference instead min-max stretches at save time via
    ``save_image(normalize=True)`` / ``make_grid(range=(-1, 1))``.
    Accepts numpy or jax arrays; returns the same family."""
    import numpy as np

    images = np.asarray(images)
    if normalization is not None:
        means, stds = (np.asarray(t, dtype=images.dtype) for t in normalization)
        images = images * stds + means
    return np.clip(images, 0.0, 1.0)


class ResBlock(nn.Module):
    """3x3 -> 3x3 -> 1x1 residual conv block (reference dalle_pytorch.py:60-72)."""

    chan: int
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        h = nn.Conv(self.chan, (3, 3), padding=1, dtype=self.dtype, param_dtype=self.param_dtype)(x)
        h = nn.relu(h)
        h = nn.Conv(self.chan, (3, 3), padding=1, dtype=self.dtype, param_dtype=self.param_dtype)(h)
        h = nn.relu(h)
        h = nn.Conv(self.chan, (1, 1), dtype=self.dtype, param_dtype=self.param_dtype)(h)
        return h + x


class DiscreteVAE(nn.Module):
    """Trainable Gumbel-softmax discrete VAE over NHWC images in [0, 1].

    Capability parity with the reference's DiscreteVAE
    (dalle_pytorch.py:74-225); all stochasticity flows through explicit keys
    (``rngs={'gumbel': key}``).
    """

    image_size: int = 256
    num_tokens: int = 512
    codebook_dim: int = 512
    num_layers: int = 3
    num_resnet_blocks: int = 0
    hidden_dim: int = 64
    channels: int = 3
    smooth_l1_loss: bool = False
    temperature: float = 0.9
    straight_through: bool = False
    kl_div_loss_weight: float = 0.0
    normalization: Optional[Tuple[tuple, tuple]] = ((0.5,) * 3, (0.5,) * 3)
    dtype: Dtype = jnp.float32
    param_dtype: Dtype = jnp.float32

    @property
    def fmap_size(self) -> int:
        return self.image_size // (2**self.num_layers)

    @property
    def image_seq_len(self) -> int:
        return self.fmap_size**2

    def setup(self):
        assert math.log2(self.image_size).is_integer(), "image size must be a power of 2"
        assert self.num_layers >= 1, "number of layers must be >= 1"

        self.codebook = nn.Embed(
            self.num_tokens, self.codebook_dim, param_dtype=self.param_dtype
        )

        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        has_res = self.num_resnet_blocks > 0

        enc = []
        for _ in range(self.num_layers):
            enc.append(nn.Conv(self.hidden_dim, (4, 4), strides=2, padding=1, **kw))
        self.enc_res = [
            ResBlock(self.hidden_dim, **kw) for _ in range(self.num_resnet_blocks)
        ]
        self.enc_convs = enc
        self.enc_out = nn.Conv(self.num_tokens, (1, 1), **kw)

        # decoder: optional 1x1 projection + resblocks first, then upsampling
        if has_res:
            self.dec_in = nn.Conv(self.hidden_dim, (1, 1), **kw)
        self.dec_res = [
            ResBlock(self.hidden_dim, **kw) for _ in range(self.num_resnet_blocks)
        ]
        dec = []
        for _ in range(self.num_layers):
            dec.append(nn.ConvTranspose(self.hidden_dim, (4, 4), strides=(2, 2), padding="SAME", **kw))
        self.dec_convs = dec
        self.dec_out = nn.Conv(self.channels, (1, 1), **kw)

    # ------------------------------------------------------------------ parts

    def norm(self, images: jnp.ndarray) -> jnp.ndarray:
        """Channelwise normalization (reference dalle_pytorch.py:154-162)."""
        if self.normalization is None:
            return images
        means, stds = (jnp.asarray(t, dtype=images.dtype) for t in self.normalization)
        return (images - means) / stds

    def encode_logits(self, img: jnp.ndarray) -> jnp.ndarray:
        """img: (b, h, w, c) in [0, 1] -> (b, f, f, num_tokens) logits."""
        x = self.norm(img).astype(self.dtype)
        for conv in self.enc_convs:
            x = nn.relu(conv(x))
        for block in self.enc_res:
            x = block(x)
        return self.enc_out(x)

    @jax.named_scope("vae.encode")
    def get_codebook_indices(self, img: jnp.ndarray) -> jnp.ndarray:
        """Hard-argmax token ids (b, f*f) — the no-grad encode used for DALL-E
        training (reference dalle_pytorch.py:164-169)."""
        logits = self.encode_logits(img)
        b = logits.shape[0]
        return jnp.argmax(logits, axis=-1).reshape(b, -1)

    def _decode_embeds(self, embeds: jnp.ndarray) -> jnp.ndarray:
        """(b, f, f, codebook_dim) codebook features -> (b, h, w, c) pixels."""
        x = embeds.astype(self.dtype)
        if self.num_resnet_blocks > 0:
            x = self.dec_in(x)
        for block in self.dec_res:
            x = block(x)
        for conv in self.dec_convs:
            x = nn.relu(conv(x))
        return self.dec_out(x)

    def decode(self, img_seq: jnp.ndarray) -> jnp.ndarray:
        """Token ids (b, n) -> pixels (reference dalle_pytorch.py:171-181)."""
        b, n = img_seq.shape
        f = int(math.isqrt(n))
        embeds = self.codebook(img_seq).reshape(b, f, f, self.codebook_dim)
        return self._decode_embeds(embeds)

    # ---------------------------------------------------------------- forward

    def __call__(
        self,
        img: jnp.ndarray,
        return_loss: bool = False,
        return_recons: bool = False,
        return_logits: bool = False,
        temp: Optional[float] = None,
    ):
        assert img.shape[1] == self.image_size and img.shape[2] == self.image_size, (
            f"input must have the correct image size {self.image_size}"
        )
        logits = self.encode_logits(img)
        if return_logits:
            return logits

        temp = self.temperature if temp is None else temp
        key = self.make_rng("gumbel")
        soft_one_hot = gumbel_softmax(
            logits, key, temperature=temp, hard=self.straight_through
        )
        # (b, f, f, num_tokens) x (num_tokens, d) -> (b, f, f, d): one matmul
        sampled = jnp.einsum(
            "bhwn,nd->bhwd",
            soft_one_hot,
            self.codebook.embedding.astype(soft_one_hot.dtype),
        )
        out = self._decode_embeds(sampled)

        if not return_loss:
            return out

        target = self.norm(img).astype(jnp.float32)
        out_f32 = out.astype(jnp.float32)
        recon_loss = (
            smooth_l1_loss(out_f32, target)
            if self.smooth_l1_loss
            else jnp.mean((out_f32 - target) ** 2)
        )

        # KL(q || uniform). The reference calls torch kl_div with a shape-(1,)
        # input and reduction='batchmean' (dalle_pytorch.py:213-220), which
        # divides by input.size(0) == 1 — i.e. the total SUM, not a mean;
        # verified against torch and preserved here.
        log_qy = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        qy = jnp.exp(log_qy)
        log_uniform = -jnp.log(float(self.num_tokens))
        kl_div = jnp.sum(qy * (log_qy - log_uniform))

        loss = recon_loss + kl_div * self.kl_div_loss_weight
        if not return_recons:
            return loss
        return loss, out
