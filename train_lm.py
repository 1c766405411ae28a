#!/usr/bin/env python
"""Causal language-model training CLI, TPU-native.

Trains ``models/lm.py:CausalLM`` — a stack described by a ``config.json`` in
its source's own keys (``--config``), by ``model_type``: Mamba-2 state-space
and grouped-KV attention layers (``granitemoehybrid``), or latent attention
with routed experts, a shared expert and a multi-token-prediction module
(``joyai_llm_flash``), or gated-delta-rule linear attention beside gated
softmax attention with softmax-routed experts and a gated shared expert
(``qwen3_next``), or sliding-window attention beside global attention with
ReGLU experts routed from the block's input (``smallthinker``), or the delta
rule with a per-channel decay beside latent attention with no positional term,
with sigmoid-routed experts (``kimi_linear``) — on a folder of ``.txt`` documents packed end to end,
with the app surface of train_clip.py and train_dalle.py's loop
(``parallel/loop.py``): compiled sharded train step over a dp x fsdp x tp mesh
(``make_runtime`` → ``create_train_state`` → ``make_train_step``), one
dispatch in flight with the step's verdict read before the next (a
device-rejected non-finite step is retried, ``--nan_abort_after`` consecutive
ones abort), ``train.*`` telemetry spans, checkpoint/resume carrying all
hparams and the Adam moments, pre-flight save.

``build_model`` and ``build_step`` are the step's whole construction; the
benchmark's driver (benchmarks/drivers/train_lm.py) calls the same two.
"""

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np
import optax


def parse_args():
    parser = argparse.ArgumentParser(description="Train a causal language model on TPU")
    parser.add_argument("--config", type=str, required=True,
                        help="the model's config.json (the source's own keys, by its "
                             "model_type: hidden_size, layer_types, mamba_*, ... or "
                             "q_lora_rank, n_routed_experts, first_k_dense_replace, ... or "
                             "full_attention_interval, linear_*, num_experts, ... or "
                             "sliding_window_layout, rope_layout, moe_num_primary_experts, ... or "
                             "linear_attn_config, mla_use_nope, num_experts_per_token, ...)")
    parser.add_argument("--image_text_folder", type=str, required=True,
                        help="folder whose .txt files are the documents (images, if any, are ignored)")
    parser.add_argument("--lm_path", type=str, default=None,
                        help="path to a partially trained model to resume")
    parser.add_argument("--lm_output_file_name", type=str, default="lm")
    parser.add_argument("--chinese", action="store_true")
    parser.add_argument("--hug", action="store_true")
    parser.add_argument("--bpe_path", type=str, default=None)
    parser.add_argument("--fp16", "--bf16", dest="bf16", action="store_true")
    parser.add_argument("--wandb", action="store_true")
    parser.add_argument("--wandb_name", default="lm_train")
    parser.add_argument("--seed", type=int, default=42)

    mesh_group = parser.add_argument_group("Mesh settings")
    mesh_group.add_argument("--fsdp", type=int, default=1)
    mesh_group.add_argument("--tp", type=int, default=1)

    model_group = parser.add_argument_group("Model settings")
    model_group.add_argument("--text_seq_len", type=int, default=8192)
    model_group.add_argument("--remat", action="store_true",
                             help="recompute each block's activations in backward")

    train_group = parser.add_argument_group("Training settings")
    train_group.add_argument("--epochs", default=20, type=int)
    train_group.add_argument("--save_every_n_steps", default=1000, type=int)
    train_group.add_argument("--batch_size", default=1, type=int)
    train_group.add_argument("--learning_rate", default=3e-4, type=float)
    train_group.add_argument("--clip_grad_norm", default=0.5, type=float)
    train_group.add_argument("--nan_abort_after", default=5, type=int)
    train_group.add_argument("--telemetry", action="store_true")
    train_group.add_argument("--telemetry_dir", default=None, type=str)
    train_group.add_argument("--metrics_port", default=None, type=int)
    return parser.parse_args()


def build_model(config: dict, seq_len: int, bf16: bool, remat: bool):
    from dalle_pytorch_tpu.models.lm import CausalLM

    return CausalLM.from_config(
        config, seq_len=seq_len, remat=remat,
        dtype=jnp.bfloat16 if bf16 else jnp.float32,
    )


def build_step(lm, params, runtime, clip_grad_norm: float):
    """-> (state, shardings, step_fn): ``step_fn(state, {"ids"}, rng, lr)``.
    Adam with the learning rate as a step argument, as train_dalle.py's."""
    from dalle_pytorch_tpu.parallel import create_train_state, make_train_step

    optimizer = optax.chain(
        optax.clip_by_global_norm(clip_grad_norm), optax.scale_by_adam(),
    )
    state, shardings = create_train_state(params, optimizer, runtime)

    # expert layers: the step also writes what they were sent and, where they
    # have one, moves their selection bias against it (CausalLM.balance)
    balances = "experts" in (lm.ff_types or ())

    def loss_fn(p, batch, rng):
        if balances:
            return lm.loss_and_loads(p, batch["ids"])
        return lm.apply({"params": p}, batch["ids"], return_loss=True)

    step_fn = make_train_step(
        loss_fn, optimizer, runtime, shardings, dynamic_lr=True,
        after_update=lm.balance if balances else None,
    )
    return state, shardings, step_fn


def main():
    args = parse_args()

    from dalle_pytorch_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    from dalle_pytorch_tpu.data import (
        ChineseTokenizer,
        DataLoader,
        HugTokenizer,
        PackedTextDataset,
        SimpleTokenizer,
    )
    from dalle_pytorch_tpu.models.factory import (
        lm_from_checkpoint,
        restore_opt_state,
        save_lm_checkpoint,
    )
    from dalle_pytorch_tpu.parallel import TrainLoop, init_distributed, make_runtime, shard_pytree
    from dalle_pytorch_tpu.utils import TELEMETRY, MetricsLogger, Throughput, counters, gauges

    init_distributed()
    runtime = make_runtime(fsdp=args.fsdp, tp=args.tp)
    runtime.check_batch_size(args.batch_size)

    if args.chinese:
        tokenizer = ChineseTokenizer()
    elif args.hug:
        tokenizer = HugTokenizer(args.bpe_path)
    else:
        tokenizer = SimpleTokenizer(args.bpe_path)

    if args.lm_path:
        lm, params, meta = lm_from_checkpoint(args.lm_path)
        start_epoch = int(meta.get("epoch", -1)) + 1
    else:
        with open(args.config) as fh:
            lm = build_model(json.load(fh), args.text_seq_len, args.bf16, args.remat)
        params = jax.jit(lm.init)(
            jax.random.key(args.seed), jnp.zeros((1, lm.seq_len), jnp.int32)
        )["params"]
        start_epoch = 0
    assert tokenizer.vocab_size <= lm.vocab_size, (
        f"the tokenizer's {tokenizer.vocab_size} ids do not fit the model's "
        f"vocabulary of {lm.vocab_size}"
    )

    dataset = PackedTextDataset(args.image_text_folder, lm.seq_len, tokenizer)
    assert len(dataset) > 0, (
        f"{args.image_text_folder} packs into no row of {lm.seq_len} tokens"
    )
    loader = DataLoader(
        dataset, args.batch_size, shuffle=True, seed=args.seed,
        process_index=runtime.process_index, process_count=runtime.process_count,
        collate_fn=PackedTextDataset.collate,
    )

    logger = MetricsLogger(
        project="lm_train", run_name=args.wandb_name, config=vars(args),
        enabled=runtime.is_root_worker(), use_wandb=args.wandb,
    )
    if args.telemetry:
        TELEMETRY.configure(
            enabled=runtime.is_root_worker(),
            flight_dir=args.telemetry_dir or f"{args.lm_output_file_name}-telemetry",
            metrics_port=args.metrics_port,
        )
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    logger.log_text(f"CausalLM {n_params:,} params | mesh {dict(runtime.mesh.shape)}")

    state, shardings, step_fn = build_step(lm, params, runtime, args.clip_grad_norm)
    del params
    if args.lm_path:
        # keep Adam moments across resume (same contract as train_dalle.py)
        host_opt = restore_opt_state(
            args.lm_path, jax.tree_util.tree_map(np.asarray, state.opt_state)
        )
        if host_opt is not None:
            state = state._replace(opt_state=shard_pytree(host_opt, shardings.opt_state))

    ckpt_path = f"{args.lm_output_file_name}.ckpt"

    def save(epoch):
        with TELEMETRY.span("train.ckpt_save", kind="full", epoch=epoch):
            host_params = runtime.to_host(loop.state.params)
            host_opt = runtime.to_host(loop.state.opt_state)
            if runtime.is_root_worker():
                save_lm_checkpoint(
                    ckpt_path, lm, host_params, extra={"epoch": epoch}, opt_state=host_opt,
                )

    # one dispatch in flight, a device-rejected batch retried under its own
    # rng key, the abort after --nan_abort_after in a row (parallel/loop.py)
    loop = TrainLoop(
        step_fn, state, feed=lambda batch: {"ids": jnp.asarray(batch["ids"])},
        lr=args.learning_rate, nan_abort_after=args.nan_abort_after,
        log=logger.log_text, on_abort=lambda _: logger.finish(),
    )
    del state  # donated by the first step: loop.state is the live one

    save(start_epoch - 1)  # pre-flight: fail fast on misconfiguration

    throughput = Throughput(window=10)
    routing_stats = jax.jit(lm.routing_stats)
    for epoch in range(start_epoch, args.epochs):
        for _, _, loss in loop.epoch(epoch, loader):
            global_step = loop.global_step
            if global_step % 10 == 0:
                logger.log({"loss": float(loss), "epoch": epoch}, step=global_step)
                logger.log_text(f"step {global_step}: loss={float(loss):.4f} epoch={epoch}")
                if args.telemetry:
                    # what the step sent the expert layers held here ({} without them)
                    stats = routing_stats(loop.state.params)
                    if stats:
                        counters.inc("moe.pairs_here", int(stats["moe.pairs_here"]))
                        gauges.set("moe.load_max_over_mean", float(stats["moe.load_max_over_mean"]))
                        if "moe.aux_loss" in stats:
                            gauges.set("moe.aux_loss", float(stats["moe.aux_loss"]))
            rate = throughput.update(args.batch_size * lm.seq_len)
            if rate is not None:
                logger.log({"tokens_per_sec": rate}, step=global_step)
            if global_step % args.save_every_n_steps == args.save_every_n_steps - 1:
                loop.resolve()  # the save holds the in-flight step's outcome
                save(epoch)
        save(epoch)
        logger.log_text(f"epoch {epoch} complete")

    logger.finish()


if __name__ == "__main__":
    main()
