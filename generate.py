#!/usr/bin/env python
"""Inference CLI: text -> images from a trained DALL-E checkpoint.

Mirrors the reference ``generate.py`` surface: checkpoint carries all hparams
(no model flags needed), prompts split on '|', batched generation, numbered
outputs per prompt under --outputs_dir, optional text completion (--gentxt).

Image generation runs through the continuous-batching serving ENGINE
(dalle_pytorch_tpu/serving): each image is a ``Request`` with its own seed,
decoded over the paged KV cache with admission control and typed outcomes —
the CLI exercises the same code path production serving does, instead of a
parallel one-shot path that only looks similar. Models the engine cannot
serve (gMLP layers) fall back to the fused scan decoder
(models/sampling.py) with a printed note.

The checkpoint is refused unless it verifies against its manifest sidecar
(sha256+size, utils/checkpoint.py) — a torn or bit-rotted file exits with a
typed error instead of deserializing garbage.
"""

import argparse
import sys
from pathlib import Path


def parse_args():
    parser = argparse.ArgumentParser(description="Generate images from a DALL-E checkpoint")
    parser.add_argument("--dalle_path", type=str, required=True)
    parser.add_argument("--text", type=str, required=True,
                        help="prompt(s); multiple prompts split on |")
    parser.add_argument("--num_images", type=int, default=128)
    parser.add_argument("--batch_size", type=int, default=4)
    parser.add_argument("--top_k", type=float, default=0.9,
                        help="fractional top-k filter threshold (reference top_k thres)")
    parser.add_argument("--temperature", type=float, default=1.0)
    parser.add_argument("--outputs_dir", type=str, default="./outputs")
    parser.add_argument("--bpe_path", type=str, default=None)
    parser.add_argument("--hug", action="store_true")
    parser.add_argument("--chinese", action="store_true")
    parser.add_argument("--gentxt", action="store_true",
                        help="complete the prompt with the model before generating images")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--fp16", "--bf16", dest="bf16", action="store_true",
                        help="serve in bf16: halves HBM weight traffic, the "
                             "decode bottleneck (analog of the reference's "
                             "fp16 generation)")
    parser.add_argument("--int8", action="store_true",
                        help="weight-only int8 serving: quantize the Dense "
                             "kernels per output channel at load time, "
                             "halving weight reads again vs bf16 (the "
                             "reference has no quantized path)")
    # local weight files for checkpoints trained against a frozen pretrained
    # VAE (whose weights are not bundled in the DALLE checkpoint)
    parser.add_argument("--vqgan_model_path", type=str, default=None)
    parser.add_argument("--vqgan_config_path", type=str, default=None)
    parser.add_argument("--openai_enc_path", type=str, default=None)
    parser.add_argument("--openai_dec_path", type=str, default=None)
    parser.add_argument("--clip_path", type=str, default=None,
                        help="CLIP checkpoint (train_clip.py) to score "
                             "generations; images are saved best-first "
                             "(reference generate_images clip rerank, "
                             "dalle_pytorch.py:503-505)")
    return parser.parse_args()


def _engine_images(engine, dalle, prompt_row, num_images, tag, seed):
    """Generate ``num_images`` images for one prompt through the (shared,
    reused across prompts) serving engine and its post-decode pipeline:
    one Request per image, each with its own (seed, position)-addressed
    sampling stream and a per-prompt ``tag`` namespacing its id. Tokens,
    the VAE decode, and (when the engine carries a CLIP) the rerank score
    all come back on the RequestResult — the CLI and production serving
    share ONE rerank path (serving/postdecode.py). Every request must
    COMPLETE here (no deadlines, roomy stage queue, default pool) — any
    other outcome, including a typed-degraded one, is a bug surfaced as a
    RuntimeError, never a silently missing image."""
    import numpy as np

    from dalle_pytorch_tpu.serving import Outcome, Request

    ids = [f"{tag}-img{i}" for i in range(num_images)]
    for i, rid in enumerate(ids):
        rejected = engine.submit(Request(
            request_id=rid,
            prompt=np.asarray(prompt_row, np.int32),
            max_new_tokens=dalle.image_seq_len,
            seed=seed + i,
        ))
        assert rejected is None, rejected
    results = engine.run()
    bad = {
        rid: results[rid].outcome.value for rid in ids
        if results[rid].outcome is not Outcome.COMPLETED
    }
    if bad:
        raise RuntimeError(f"engine failed requests: {bad}")
    # a COMPLETED outcome says the state machine finished, not that the
    # device computed sense: a token outside the image vocabulary or a
    # non-finite pixel would otherwise be written out as a valid PNG
    tokens = np.stack([results[rid].tokens for rid in ids])
    if tokens.min() < 0 or tokens.max() >= dalle.num_image_tokens:
        raise RuntimeError(
            f"engine sampled tokens outside [0, {dalle.num_image_tokens}): "
            f"min {tokens.min()}, max {tokens.max()}"
        )
    images = np.stack([results[rid].image for rid in ids])
    if not np.isfinite(images).all():
        raise RuntimeError("VAE decode stage produced non-finite pixels")
    scores = None
    if engine.postdecode is not None and engine.postdecode.rerank:
        scores = np.asarray(
            [results[rid].rerank_score for rid in ids], np.float32
        )
    return images, scores


def main():
    args = parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from PIL import Image

    from dalle_pytorch_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    from dalle_pytorch_tpu.data import ChineseTokenizer, HugTokenizer, SimpleTokenizer
    from dalle_pytorch_tpu.models import generate_image_tokens, generate_texts
    from dalle_pytorch_tpu.models.factory import dalle_from_checkpoint
    from dalle_pytorch_tpu.models.vae import denormalize
    from dalle_pytorch_tpu.serving import EngineUnsupportedModel
    from dalle_pytorch_tpu.utils.checkpoint import (
        CheckpointError, check_checkpoint_file,
    )

    try:
        check_checkpoint_file(args.dalle_path)
    except CheckpointError as e:
        print(f"ERROR: {e}", file=sys.stderr)
        print(
            "refusing to load an unverifiable checkpoint; regenerate it or "
            "restore from a verified save", file=sys.stderr,
        )
        sys.exit(2)
    dalle, params, vae, vae_params, meta = dalle_from_checkpoint(
        args.dalle_path,
        vae_weight_paths={
            k: getattr(args, k)
            for k in (
                "openai_enc_path", "openai_dec_path",
                "vqgan_config_path", "vqgan_model_path",
            )
        },
    )
    assert vae is not None, "checkpoint carries no VAE — cannot decode images"

    if args.bf16 or args.int8:
        from dalle_pytorch_tpu.utils.quantize import prepare_for_serving

        dalle, params = prepare_for_serving(dalle, params, int8=args.int8)

    if args.chinese:
        tokenizer = ChineseTokenizer()
    elif args.hug:
        tokenizer = HugTokenizer(args.bpe_path)
    else:
        tokenizer = SimpleTokenizer(args.bpe_path)
    print(f"tokenizer: {type(tokenizer).__name__}")

    clip = clip_params = None
    if args.clip_path:
        from dalle_pytorch_tpu.models.factory import clip_from_checkpoint

        clip, clip_params, _ = clip_from_checkpoint(args.clip_path)

    texts = [t.strip() for t in args.text.split("|") if t.strip()]
    outputs_dir = Path(args.outputs_dir)

    key = jax.random.key(args.seed)

    # ONE engine reused across prompts (the decode caches are allocated at
    # construction). The VAE decode and CLIP rerank ride as post-decode
    # STAGES on the engine (serving/postdecode.py) so the CLI and
    # production serving share one request→image path; the stage queue is
    # sized to the full image count so no request ever hits the typed
    # backlog-degrade policy here. gMLP models get the fused-scan fallback
    # with an ad-hoc decode/rerank instead.
    engine = None
    try:
        from dalle_pytorch_tpu.serving import (
            Engine, EngineConfig, StageConfig, StageSpec,
        )

        engine = Engine(
            dalle, params,
            EngineConfig(
                max_batch=args.batch_size,
                queue_limit=max(args.num_images, 1),
                filter_thres=args.top_k,
                temperature=args.temperature,
            ),
            stages=StageSpec(
                vae, vae_params, clip, clip_params,
                config=StageConfig(
                    batch=args.batch_size,
                    queue_limit=max(args.num_images, 1),
                ),
            ),
        )
    except EngineUnsupportedModel as e:
        print(
            f"serving engine unavailable for this model ({e}); "
            "falling back to the fused scan decoder",
            file=sys.stderr,
        )

    decode = None
    if engine is None:
        decode = jax.jit(
            lambda seq: vae.apply({"params": vae_params}, seq, method="decode")
        )

    for pi, text in enumerate(texts):
        if args.gentxt:
            prompt_ids = jnp.asarray([tokenizer.encode(text)], jnp.int32)
            key, sub = jax.random.split(key)
            _, completed = generate_texts(
                dalle, params, sub, prompt_ids, tokenizer=tokenizer,
                filter_thres=args.top_k, temperature=args.temperature,
            )
            text = completed[0].strip() if completed else text
            print(f"completed prompt: {text}")

        prompt_row = np.asarray(
            tokenizer.tokenize([text], dalle.text_seq_len, truncate_text=True)
        )[0]

        if engine is not None:
            images, scores = _engine_images(
                engine, dalle, prompt_row, args.num_images, tag=f"p{pi}",
                seed=args.seed * 1_000_003 + pi * 65_537,
            )
            images = denormalize(images, getattr(vae, "normalization", None))
            if scores is not None:
                # rerank: save best-scoring generations first (reference
                # dalle_pytorch.py:503-505); the scores were produced by
                # the engine's post-decode stage, so the CLI ordering and
                # serving's rerank agree bit-for-bit
                images = images[np.argsort(-scores)]
        else:
            tokens = jnp.asarray(
                np.repeat(prompt_row[None], args.batch_size, axis=0)
            )
            chunks = []
            for _ in range(-(-args.num_images // args.batch_size)):
                key, sub = jax.random.split(key)
                chunks.append(np.asarray(generate_image_tokens(
                    dalle, params, tokens, sub,
                    filter_thres=args.top_k, temperature=args.temperature,
                )))
            seqs = np.concatenate(chunks)[: args.num_images]

            images = []
            for s in range(0, len(seqs), args.batch_size):
                chunk = seqs[s : s + args.batch_size]
                n = len(chunk)
                if n < args.batch_size:  # pad ragged tail for the jit shape
                    chunk = np.concatenate(
                        [chunk,
                         np.repeat(chunk[-1:], args.batch_size - n, axis=0)]
                    )
                images.append(np.asarray(decode(jnp.asarray(chunk)))[:n])
            images = np.concatenate(images)

            images = denormalize(images, getattr(vae, "normalization", None))

            if clip is not None:
                # fallback-only ad-hoc rerank (the engine path gets its
                # scores from the shared post-decode stage instead)
                clip_imgs = jax.image.resize(
                    jnp.asarray(images),
                    (len(images), clip.visual_image_size,
                     clip.visual_image_size, 3),
                    method="bilinear",
                )
                clip_text = jnp.asarray(
                    tokenizer.tokenize(
                        [text], clip.text_seq_len, truncate_text=True
                    )
                ).repeat(len(images), axis=0)
                scores = clip.apply(
                    {"params": clip_params}, clip_text, clip_imgs,
                    text_mask=clip_text != 0,
                )
                images = images[np.argsort(-np.asarray(scores))]

        sub_dir = outputs_dir / text.replace(" ", "_")[:100]
        sub_dir.mkdir(parents=True, exist_ok=True)
        for i, arr in enumerate(images):
            Image.fromarray((arr * 255).astype(np.uint8)).save(
                sub_dir / f"{i}.png"
            )
        (sub_dir / "caption.txt").write_text(text)
        print(f"created {len(images)} images at '{sub_dir}'")


if __name__ == "__main__":
    main()
