"""Model reconstitution from checkpoints.

The reference snapshots model hparams inside every checkpoint so generation
needs no flag re-specification (train_dalle.py:514-517, generate.py:81-95).
Same contract here: the plain checkpoint carries ``meta`` with the model-class
name and constructor kwargs plus (for DALLE) the VAE class/params, and these
helpers rebuild modules + params from a path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax.numpy as jnp

from ..utils.checkpoint import load_checkpoint, save_checkpoint
from .dalle import DALLE
from .pretrained import OpenAIDiscreteVAE
from .vae import DiscreteVAE

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


def vae_classes() -> dict:
    """Name -> class for every VAE family a checkpoint may carry (the
    reference's generate.py:86-91 three-way switch)."""
    from .vqgan import VQGanVAE

    return {
        "DiscreteVAE": DiscreteVAE,
        "OpenAIDiscreteVAE": OpenAIDiscreteVAE,
        "VQGanVAE": VQGanVAE,
    }


def deep_merge(a: dict, b: dict) -> dict:
    """Recursive dict merge (b wins on leaves) — sub-path inits (encode-only
    / decode-only) can both contribute children to the same submodule."""
    out = dict(a)
    for k, v in b.items():
        out[k] = (
            deep_merge(out[k], v)
            if isinstance(v, dict) and isinstance(out.get(k), dict)
            else v
        )
    return out


def init_vae_params(vae) -> Any:
    """A zeroed param tree with the right structure for ``vae`` — the
    from_state_dict restore target. Trainable DiscreteVAE inits through
    __call__ (needs a gumbel key); frozen wrappers init their enc/dec paths
    via the method-based entry points."""
    import jax

    key = jax.random.key(0)
    if isinstance(vae, DiscreteVAE):
        img = jnp.zeros((1, vae.image_size, vae.image_size, vae.channels))
        shapes = jax.eval_shape(
            lambda: vae.init({"params": key, "gumbel": key}, img)
        )["params"]
    else:
        img = jnp.zeros((1, vae.image_size, vae.image_size, 3))
        seq = jnp.zeros((1, vae.image_seq_len), jnp.int32)
        shapes = deep_merge(
            jax.eval_shape(
                lambda: vae.init(key, img, method="get_codebook_indices")
            )["params"],
            jax.eval_shape(lambda: vae.init(key, seq, method="decode"))["params"],
        )
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def _config_dict(module) -> dict:
    """Constructor kwargs of a flax module (dataclass fields), with dtypes
    stringified for json."""
    out = {}
    for f in dataclasses.fields(module):
        if f.name in ("parent", "name"):
            continue
        v = getattr(module, f.name)
        if v in (jnp.float32, jnp.bfloat16, jnp.float16):
            v = jnp.dtype(v).name
        out[f.name] = v
    return out


def _restore_dtypes(cfg: dict) -> dict:
    cfg = dict(cfg)
    for k in ("dtype", "param_dtype"):
        if isinstance(cfg.get(k), str):
            cfg[k] = _DTYPES[cfg[k]]
    for k in ("attn_types", "layer_types", "ff_types", "experts_held"):
        if isinstance(cfg.get(k), list):
            cfg[k] = tuple(cfg[k])
    if "normalization" in cfg and isinstance(cfg["normalization"], list):
        cfg["normalization"] = tuple(tuple(x) for x in cfg["normalization"])
    if "shape" in cfg and isinstance(cfg["shape"], list):
        cfg["shape"] = tuple(cfg["shape"])
    return cfg


# ------------------------------------------------------------------- VAE


def save_vae_checkpoint(path: str, vae, params: Any, extra: Optional[dict] = None):
    meta = {
        "model_class": type(vae).__name__,
        "config": _config_dict(vae),
        **(extra or {}),
    }
    save_checkpoint(path, {"params": params}, meta)


def vae_from_checkpoint(path: str) -> Tuple[Any, Any, dict]:
    state, meta = load_checkpoint(path)
    classes = vae_classes()
    cls = classes.get(meta.get("model_class"))
    assert cls is not None, f"not a VAE checkpoint: {meta.get('model_class')}"
    vae = cls(**_restore_dtypes(meta["config"]))
    from flax import serialization

    params = serialization.from_state_dict(
        init_vae_params(vae), state["params"]
    )
    return vae, params, meta


# ------------------------------------------------------------------ DALLE


def save_dalle_checkpoint(
    path: str,
    dalle: DALLE,
    params: Any,
    vae: Optional[DiscreteVAE] = None,
    vae_params: Any = None,
    extra: Optional[dict] = None,
    opt_state: Any = None,
    step: Any = None,
):
    """Plain single-file DALLE checkpoint bundling the frozen VAE and (when
    given) the optimizer state — the reference's {hparams, vae_params, epoch,
    weights, opt_state, scheduler_state} layout (train_dalle.py:514-519)."""
    meta = {
        "model_class": "DALLE",
        "config": _config_dict(dalle),
        **(extra or {}),
    }
    state = {"params": params}
    if vae is not None:
        meta["vae_class"] = type(vae).__name__
        meta["vae_config"] = _config_dict(vae)
        if isinstance(vae, DiscreteVAE):
            state["vae_params"] = vae_params
        # frozen pretrained wrappers (OpenAI dVAE / VQGAN) are NOT bundled:
        # their weights are immutable public downloads, and re-serializing
        # ~100s of MB into every periodic checkpoint would dominate save
        # latency — the loader reconstitutes them from the weight cache
        # (reference does the same: generate.py:86-91 re-instantiates by
        # class and the weights come from ~/.cache)
    if opt_state is not None:
        state["opt_state"] = opt_state
        meta["has_opt_state"] = True
    if step is not None:
        state["step"] = step
    save_checkpoint(path, state, meta)


def restore_opt_state(path: str, target: Any) -> Optional[Any]:
    """Restore the optimizer state saved by ``save_dalle_checkpoint`` /
    ``save_clip_checkpoint`` into ``target``'s structure (None when the
    checkpoint carries none), so resume keeps Adam moments instead of
    silently resetting them."""
    from flax import serialization

    state, meta = load_checkpoint(path)
    if not meta.get("has_opt_state"):
        return None
    return serialization.from_state_dict(target, state["opt_state"])


def _restore_params(module, init_args: Tuple[Any, ...], state_params: Any) -> Any:
    """Shape-inferred zero tree for ``module.init(*init_args)`` filled from a
    checkpoint's params state dict — the one restore idiom shared by the
    DALLE and CLIP loaders."""
    import jax
    from flax import serialization

    shapes = jax.eval_shape(
        lambda: module.init(jax.random.key(0), *init_args)
    )["params"]
    zeros = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    return serialization.from_state_dict(zeros, state_params)


def dalle_from_checkpoint(path: str, vae_weight_paths: Optional[dict] = None):
    """-> (dalle, params, vae, vae_params, meta); vae is None when the
    checkpoint carries no VAE.

    Frozen pretrained VAEs (OpenAI dVAE / VQGAN) are stored by class+config
    only; their weights are reconstituted from the download cache, or from
    local files given in ``vae_weight_paths`` (keys: ``openai_enc_path``,
    ``openai_dec_path``, ``vqgan_config_path``, ``vqgan_model_path``)."""
    import jax
    from flax import serialization

    state, meta = load_checkpoint(path)
    assert meta.get("model_class") == "DALLE", (
        f"not a DALLE checkpoint: {meta.get('model_class')}"
    )
    dalle = DALLE(**_restore_dtypes(meta["config"]))
    text = jnp.zeros((1, dalle.text_seq_len), jnp.int32)
    image = jnp.zeros((1, dalle.image_seq_len), jnp.int32)
    params = _restore_params(dalle, (text, image), state["params"])

    vae = vae_params = None
    wp = vae_weight_paths or {}
    if "vae_config" in meta:
        vae_class = meta.get("vae_class")
        cls = vae_classes().get(vae_class)
        assert cls is not None, f"unknown VAE class {vae_class}"
        vae = cls(**_restore_dtypes(meta["vae_config"]))
        if "vae_params" in state:
            vae_params = serialization.from_state_dict(
                init_vae_params(vae), state["vae_params"]
            )
        elif vae_class == "OpenAIDiscreteVAE":
            from .pretrained import load_openai_vae

            vae, vae_params = load_openai_vae(
                wp.get("openai_enc_path"), wp.get("openai_dec_path"),
                dtype=vae.dtype,
            )
        elif vae_class == "VQGanVAE":
            from .vqgan import load_vqgan_vae

            vae, vae_params = load_vqgan_vae(
                wp.get("vqgan_config_path"), wp.get("vqgan_model_path"),
                dtype=vae.dtype,
            )
    return dalle, params, vae, vae_params, meta


# ------------------------------------------------------------------- CLIP


def save_clip_checkpoint(
    path: str,
    clip,
    params: Any,
    extra: Optional[dict] = None,
    opt_state: Any = None,
):
    """Hparams-carrying CLIP checkpoint (same shape as the DALLE format:
    {config, params[, opt_state]} so generation reranking needs no flags)."""
    meta = {
        "model_class": "CLIP",
        "config": _config_dict(clip),
        **(extra or {}),
    }
    state = {"params": params}
    if opt_state is not None:
        state["opt_state"] = opt_state
        meta["has_opt_state"] = True
    save_checkpoint(path, state, meta)


def clip_from_checkpoint(path: str) -> Tuple[Any, Any, dict]:
    """(CLIP module, params, meta) from a save_clip_checkpoint file."""
    from .clip import CLIP

    state, meta = load_checkpoint(path)
    assert meta.get("model_class") == "CLIP", (
        f"not a CLIP checkpoint: {meta.get('model_class')}"
    )
    clip = CLIP(**_restore_dtypes(meta["config"]))
    text = jnp.zeros((1, clip.text_seq_len), jnp.int32)
    image = jnp.zeros(
        (1, clip.visual_image_size, clip.visual_image_size, clip.channels)
    )
    params = _restore_params(clip, (text, image), state["params"])
    return clip, params, meta


# --------------------------------------------------------------- CausalLM


def save_lm_checkpoint(
    path: str,
    lm,
    params: Any,
    extra: Optional[dict] = None,
    opt_state: Any = None,
):
    """Hparams-carrying CausalLM checkpoint, the CLIP format's shape:
    {config, params[, opt_state]}."""
    meta = {"model_class": "CausalLM", "config": _config_dict(lm), **(extra or {})}
    state = {"params": params}
    if opt_state is not None:
        state["opt_state"] = opt_state
        meta["has_opt_state"] = True
    save_checkpoint(path, state, meta)


def lm_from_checkpoint(path: str) -> Tuple[Any, Any, dict]:
    """(CausalLM module, params, meta) from a save_lm_checkpoint file."""
    from .lm import CausalLM

    state, meta = load_checkpoint(path)
    assert meta.get("model_class") == "CausalLM", (
        f"not a CausalLM checkpoint: {meta.get('model_class')}"
    )
    lm = CausalLM(**_restore_dtypes(meta["config"]))
    ids = jnp.zeros((1, lm.seq_len), jnp.int32)
    return lm, _restore_params(lm, (ids,), state["params"]), meta
