#!/usr/bin/env python
"""DiscreteVAE training CLI, TPU-native.

Mirrors the reference's ``train_vae.py`` app surface (flags, Gumbel
temperature annealing ``max(T0·exp(-r·step), Tmin)`` every 100 steps
(train_vae.py:269-271), exponential lr decay, recon/codebook-usage logging,
per-epoch checkpoints) — rebuilt around a compiled sharded train step on a
device mesh instead of DeepSpeed/Horovod engines.
"""

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax


def parse_args():
    parser = argparse.ArgumentParser(description="Train a DiscreteVAE on TPU")
    parser.add_argument("--image_folder", type=str, required=True,
                        help="folder of images for learning the discrete VAE and its codebook")
    parser.add_argument("--image_size", type=int, default=128)

    mesh_group = parser.add_argument_group("Mesh settings")
    mesh_group.add_argument("--fsdp", type=int, default=1, help="ZeRO/param-sharding axis size")
    mesh_group.add_argument("--tp", type=int, default=1, help="tensor-parallel axis size")

    train_group = parser.add_argument_group("Training settings")
    train_group.add_argument("--epochs", type=int, default=20)
    train_group.add_argument("--batch_size", type=int, default=8)
    train_group.add_argument("--learning_rate", type=float, default=1e-3)
    train_group.add_argument("--lr_decay_rate", type=float, default=0.98)
    train_group.add_argument("--starting_temp", type=float, default=1.0)
    train_group.add_argument("--temp_min", type=float, default=0.5)
    train_group.add_argument("--anneal_rate", type=float, default=1e-6)
    train_group.add_argument("--num_images_save", type=int, default=4)
    train_group.add_argument("--seed", type=int, default=0)
    train_group.add_argument("--output_file_name", type=str, default="vae.ckpt")
    train_group.add_argument("--samples_dir", type=str, default="vae_samples")
    train_group.add_argument("--wandb", action="store_true", help="log to wandb when available")

    model_group = parser.add_argument_group("Model settings")
    model_group.add_argument("--num_tokens", type=int, default=8192)
    model_group.add_argument("--num_layers", type=int, default=3)
    model_group.add_argument("--num_resnet_blocks", type=int, default=2)
    model_group.add_argument("--smooth_l1_loss", action="store_true")
    model_group.add_argument("--emb_dim", type=int, default=512)
    model_group.add_argument("--hidden_dim", type=int, default=256)
    model_group.add_argument("--kl_loss_weight", type=float, default=0.0)
    return parser.parse_args()


def main():
    args = parse_args()

    from dalle_pytorch_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    from dalle_pytorch_tpu.data import DataLoader, ImageFolderDataset
    from dalle_pytorch_tpu.models import DiscreteVAE
    from dalle_pytorch_tpu.models.factory import save_vae_checkpoint
    from dalle_pytorch_tpu.parallel import (
        create_train_state,
        init_distributed,
        make_runtime,
        make_train_step,
    )
    from dalle_pytorch_tpu.utils import (
        ExponentialDecay,
        MetricsLogger,
        Throughput,
        gumbel_temperature,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    init_distributed()
    runtime = make_runtime(fsdp=args.fsdp, tp=args.tp)
    runtime.check_batch_size(args.batch_size)

    vae = DiscreteVAE(
        image_size=args.image_size,
        num_tokens=args.num_tokens,
        codebook_dim=args.emb_dim,
        num_layers=args.num_layers,
        num_resnet_blocks=args.num_resnet_blocks,
        hidden_dim=args.hidden_dim,
        smooth_l1_loss=args.smooth_l1_loss,
        kl_div_loss_weight=args.kl_loss_weight,
    )

    dataset = ImageFolderDataset(args.image_folder, args.image_size, seed=args.seed)
    loader = DataLoader(
        dataset,
        args.batch_size,
        shuffle=True,
        seed=args.seed,
        process_index=runtime.process_index,
        process_count=runtime.process_count,
        collate_fn=ImageFolderDataset.collate,
    )
    assert len(loader) > 0, "dataset too small for one batch"

    logger = MetricsLogger(
        project="dalle_tpu_vae",
        config=vars(args),
        enabled=runtime.is_root_worker(),
        use_wandb=args.wandb,
    )

    dummy = jnp.zeros((1, args.image_size, args.image_size, 3))
    params = jax.jit(vae.init)(
        {"params": jax.random.key(args.seed), "gumbel": jax.random.key(0)}, dummy
    )["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    logger.log_text(f"DiscreteVAE with {n_params:,} params on {runtime.world_size} devices")

    optimizer = optax.scale_by_adam()  # lr applied dynamically in the step
    state, shardings = create_train_state(params, optimizer, runtime)

    def loss_fn(p, batch, rng):
        loss, recons = vae.apply(
            {"params": p},
            batch["image"],
            return_loss=True,
            return_recons=True,
            temp=batch["temp"],
            rngs={"gumbel": rng},
        )
        return loss, recons

    replicated = NamedSharding(runtime.mesh, P())
    data_shardings = {"image": runtime.data_sharding, "temp": replicated}
    step_fn = make_train_step(
        loss_fn, optimizer, runtime, shardings,
        has_aux=True, dynamic_lr=True, data_shardings=data_shardings,
    )

    encode_fn = jax.jit(
        lambda p, img: vae.apply({"params": p}, img, method=DiscreteVAE.get_codebook_indices)
    )

    sched = ExponentialDecay(args.learning_rate, args.lr_decay_rate)
    lr = args.learning_rate
    temp = args.starting_temp
    throughput = Throughput(window=10)
    samples_dir = Path(args.samples_dir)

    global_step = 0
    for epoch in range(args.epochs):
        for batch in loader:
            batch = dict(batch, temp=jnp.asarray(temp, jnp.float32))
            state, loss, recons = step_fn(
                state, batch, jax.random.key(global_step), jnp.asarray(lr)
            )

            if global_step % 100 == 0:
                loss_v = float(loss)
                logs = {"loss": loss_v, "lr": lr, "temp": temp, "epoch": epoch}

                # codebook usage (collapse monitoring, train_vae.py:252-262):
                # the full index histogram shows the SHAPE of a collapse,
                # the unique count its headline number
                idx = np.asarray(encode_fn(state.params, batch["image"]))
                logs["codebook_used"] = int(np.unique(idx).size)
                logger.log_histogram("codebook_indices", idx, step=global_step)

                if runtime.is_root_worker():
                    from dalle_pytorch_tpu.models.vae import denormalize

                    k = min(args.num_images_save, batch["image"].shape[0])
                    samples_dir.mkdir(parents=True, exist_ok=True)
                    # recons are in the decoder's normalized space; originals
                    # are raw [0,1] — bring both to display space
                    rec = denormalize(recons[:k], vae.normalization)
                    orig = np.asarray(batch["image"][:k])
                    grid = np.concatenate(
                        [np.concatenate(list(orig), 1), np.concatenate(list(rec), 1)], 0
                    )
                    from PIL import Image

                    Image.fromarray((grid * 255).astype(np.uint8)).save(
                        samples_dir / f"recon_{global_step:07d}.png"
                    )
                    logger.log_images("reconstructions", rec, step=global_step)

                temp = gumbel_temperature(
                    global_step, args.starting_temp, args.anneal_rate, args.temp_min
                )
                logger.log(logs, step=global_step)

            rate = throughput.update(args.batch_size)
            if rate is not None:
                logger.log({"sample_per_sec": rate}, step=global_step)
            global_step += 1

        lr = sched.step()
        host_params = runtime.to_host(state.params)  # collective gather
        if runtime.is_root_worker():
            save_vae_checkpoint(
                args.output_file_name, vae, host_params,
                extra={"epoch": epoch, "scheduler_state": sched.state_dict()},
            )
            logger.log_text(f"epoch {epoch} done; saved {args.output_file_name}")
        # per-epoch model artifact (reference train_vae.py:298-313); the
        # logger is root-gated via enabled=
        logger.log_artifact("trained-vae", args.output_file_name, metadata=vars(args))

    logger.finish()


if __name__ == "__main__":
    main()
