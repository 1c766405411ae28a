#!/usr/bin/env python
"""CLIP training CLI, TPU-native.

The reference ships a trainable ``CLIP`` (dalle_pytorch.py:229-305) and uses
it to rerank generations (generate_images clip=..., dalle_pytorch.py:503-505)
but provides no training app for it — its README trains CLIP with an
inline-code block only. This CLI closes that gap with the same app surface as
train_dalle.py: folder dataset of image + same-stem caption files, compiled
sharded train step over a dp x fsdp x tp mesh, checkpoint/resume carrying all
hparams, wandb/console metrics, pre-flight save. The resulting checkpoint
plugs into ``generate.py --clip_path`` for sampling-time reranking.
"""

import argparse
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax


def parse_args():
    parser = argparse.ArgumentParser(description="Train CLIP on TPU")
    parser.add_argument("--image_text_folder", type=str, required=True,
                        help="folder of images + same-stem .txt captions")
    parser.add_argument("--clip_path", type=str, default=None,
                        help="path to a partially trained CLIP to resume")
    parser.add_argument("--clip_output_file_name", type=str, default="clip")
    parser.add_argument("--truncate_captions", action="store_true")
    parser.add_argument("--chinese", action="store_true")
    parser.add_argument("--hug", action="store_true")
    parser.add_argument("--bpe_path", type=str, default=None)
    parser.add_argument("--fp16", "--bf16", dest="bf16", action="store_true")
    parser.add_argument("--wandb", action="store_true")
    parser.add_argument("--wandb_name", default="clip_train")
    parser.add_argument("--seed", type=int, default=42)

    mesh_group = parser.add_argument_group("Mesh settings")
    mesh_group.add_argument("--fsdp", type=int, default=1)
    mesh_group.add_argument("--tp", type=int, default=1)

    model_group = parser.add_argument_group("Model settings")
    model_group.add_argument("--dim_text", type=int, default=512)
    model_group.add_argument("--dim_image", type=int, default=512)
    model_group.add_argument("--dim_latent", type=int, default=512)
    model_group.add_argument("--text_enc_depth", type=int, default=6)
    model_group.add_argument("--text_seq_len", type=int, default=256)
    model_group.add_argument("--text_heads", type=int, default=8)
    model_group.add_argument("--visual_enc_depth", type=int, default=6)
    model_group.add_argument("--visual_heads", type=int, default=8)
    model_group.add_argument("--visual_image_size", type=int, default=256)
    model_group.add_argument("--visual_patch_size", type=int, default=32)

    train_group = parser.add_argument_group("Training settings")
    train_group.add_argument("--epochs", default=20, type=int)
    train_group.add_argument("--save_every_n_steps", default=1000, type=int)
    train_group.add_argument("--batch_size", default=32, type=int)
    train_group.add_argument("--learning_rate", default=3e-4, type=float)
    train_group.add_argument("--clip_grad_norm", default=0.5, type=float)
    return parser.parse_args()


def main():
    args = parse_args()

    from dalle_pytorch_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    from dalle_pytorch_tpu.data import (
        ChineseTokenizer,
        DataLoader,
        HugTokenizer,
        SimpleTokenizer,
        TextImageDataset,
    )
    from dalle_pytorch_tpu.models.clip import CLIP
    from dalle_pytorch_tpu.models.factory import clip_from_checkpoint, save_clip_checkpoint
    from dalle_pytorch_tpu.parallel import (
        create_train_state,
        init_distributed,
        make_runtime,
        make_train_step,
    )
    from dalle_pytorch_tpu.utils import MetricsLogger, Throughput

    init_distributed()
    runtime = make_runtime(fsdp=args.fsdp, tp=args.tp)
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32

    if args.chinese:
        tokenizer = ChineseTokenizer()
    elif args.hug:
        tokenizer = HugTokenizer(args.bpe_path)
    else:
        tokenizer = SimpleTokenizer(args.bpe_path)

    if args.clip_path:
        clip, resume_params, meta = clip_from_checkpoint(args.clip_path)
        start_epoch = int(meta.get("epoch", -1)) + 1
        if clip.dtype != dtype:
            clip = clip.clone(dtype=dtype)
    else:
        clip = CLIP(
            dim_text=args.dim_text,
            dim_image=args.dim_image,
            dim_latent=args.dim_latent,
            num_text_tokens=tokenizer.vocab_size,
            text_enc_depth=args.text_enc_depth,
            text_seq_len=args.text_seq_len,
            text_heads=args.text_heads,
            visual_enc_depth=args.visual_enc_depth,
            visual_heads=args.visual_heads,
            visual_image_size=args.visual_image_size,
            visual_patch_size=args.visual_patch_size,
            dtype=dtype,
        )
        resume_params = None
        start_epoch = 0

    dataset = TextImageDataset(
        args.image_text_folder,
        text_len=clip.text_seq_len,
        image_size=clip.visual_image_size,
        truncate_captions=args.truncate_captions,
        tokenizer=tokenizer,
        shuffle=True,
        seed=args.seed,
    )
    assert len(dataset) > 0, f"no image-text pairs found at {args.image_text_folder}"
    loader = DataLoader(
        dataset,
        args.batch_size,
        shuffle=True,
        seed=args.seed,
        process_index=runtime.process_index,
        process_count=runtime.process_count,
    )

    logger = MetricsLogger(
        project="clip_train",
        run_name=args.wandb_name,
        config=vars(args),
        enabled=runtime.is_root_worker(),
        use_wandb=args.wandb,
    )

    text0 = jnp.zeros((2, clip.text_seq_len), jnp.int32)
    image0 = jnp.zeros(
        (2, clip.visual_image_size, clip.visual_image_size, clip.channels)
    )
    if resume_params is not None:
        params = resume_params
    else:
        params = jax.jit(clip.init)(jax.random.key(args.seed), text0, image0)["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    logger.log_text(f"CLIP {n_params:,} params | mesh {dict(runtime.mesh.shape)}")

    optimizer = optax.chain(
        optax.clip_by_global_norm(args.clip_grad_norm),
        optax.adam(args.learning_rate),
    )
    state, shardings = create_train_state(params, optimizer, runtime)
    if args.clip_path:
        # keep Adam moments across resume (same contract as train_dalle.py)
        from dalle_pytorch_tpu.models.factory import restore_opt_state
        from dalle_pytorch_tpu.parallel import shard_pytree

        host_opt = restore_opt_state(
            args.clip_path, jax.tree_util.tree_map(np.asarray, state.opt_state)
        )
        if host_opt is not None:
            state = state._replace(
                opt_state=shard_pytree(host_opt, shardings.opt_state)
            )
    del params, resume_params

    def loss_fn(p, batch, rng):
        # the text mask marks real (non-pad) tokens for masked-mean pooling
        # (reference README's CLIP block passes an explicit mask)
        return clip.apply(
            {"params": p},
            batch["text"],
            batch["image"],
            text_mask=batch["text"] != 0,
            return_loss=True,
        )

    step_fn = make_train_step(loss_fn, optimizer, runtime, shardings)

    ckpt_path = f"{args.clip_output_file_name}.ckpt"

    def save(epoch):
        host_params = runtime.to_host(state.params)
        host_opt = runtime.to_host(state.opt_state)
        if not runtime.is_root_worker():
            return
        save_clip_checkpoint(
            ckpt_path, clip, host_params,
            extra={"epoch": epoch}, opt_state=host_opt,
        )

    save(start_epoch - 1)  # pre-flight: fail fast on misconfiguration

    throughput = Throughput(window=10)
    global_step = 0
    for epoch in range(start_epoch, args.epochs):
        for i, batch in enumerate(loader):
            train_batch = {
                "text": batch["text"],
                "image": jnp.asarray(batch["image"], dtype),
            }
            state, loss = step_fn(state, train_batch, jax.random.key(global_step))

            if i % 10 == 9 or i == 0:
                logger.log(
                    {"loss": float(loss), "epoch": epoch, "iter": i},
                    step=global_step,
                )
                logger.log_text(
                    f"step {global_step}: loss={float(loss):.4f} epoch={epoch}"
                )
            rate = throughput.update(args.batch_size)
            if rate is not None:
                logger.log({"sample_per_sec": rate}, step=global_step)
            if global_step % args.save_every_n_steps == args.save_every_n_steps - 1:
                save(epoch)
            global_step += 1
        save(epoch)
        logger.log_text(f"epoch {epoch} complete")

    logger.finish()


if __name__ == "__main__":
    main()
