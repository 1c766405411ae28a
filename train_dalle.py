#!/usr/bin/env python
"""DALL-E training CLI, TPU-native.

Mirrors the reference ``train_dalle.py`` app surface (SURVEY.md §2.1): VAE
reconstitution, folder or tar-shard datasets, resume, clip-grad Adam with
optional ReduceLROnPlateau, periodic checkpoint/sample/metric emission, and a
pre-flight checkpoint save that fails fast on misconfiguration
(train_dalle.py:561-563) — around one compiled sharded train step.

Differences from the reference, by design:
- VAE encode (frozen, no-grad) runs as its own jitted call feeding image
  tokens to the train step (the reference calls it under no_grad inside
  forward, dalle_pytorch.py:533-540);
- --fp16/--amp map to bf16 (no loss scaling needed on TPU);
- DeepSpeed/Horovod backend flags become mesh axis flags (--fsdp/--tp).
"""

import argparse
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax


def build_parser():
    parser = argparse.ArgumentParser(description="Train DALL-E on TPU")
    group = parser.add_mutually_exclusive_group(required=False)
    group.add_argument("--vae_path", type=str, help="path to a trained DiscreteVAE checkpoint")
    group.add_argument("--dalle_path", type=str, help="path to a partially trained DALL-E to resume")
    parser.add_argument("--image_text_folder", type=str, required=True,
                        help="folder of images + same-stem .txt captions, or a .tar shard spec")
    parser.add_argument("--wds", type=str, nargs="?", const="auto", default="",
                        help="treat image_text_folder as a webdataset tar "
                             "shard spec. Bare --wds auto-detects the "
                             "image/caption member names; a value gives the "
                             "comma-separated image,caption column names the "
                             "reference takes (ref train_dalle.py:48-53), "
                             "e.g. --wds img,cap")
    parser.add_argument("--truncate_captions", action="store_true")
    parser.add_argument("--random_resize_crop_lower_ratio", dest="resize_ratio",
                        type=float, default=0.75)
    parser.add_argument("--chinese", action="store_true")
    parser.add_argument("--hug", action="store_true")
    parser.add_argument("--bpe_path", type=str, default=None)
    parser.add_argument("--taming", action="store_true",
                        help="use a pretrained VQGAN (taming) instead of a "
                             "trained DiscreteVAE; the default f=16 model "
                             "cuts image seq 1024 -> 256")
    parser.add_argument("--vqgan_model_path", type=str, default=None,
                        help="local taming checkpoint (.ckpt); downloads the "
                             "published f16/1024 model when omitted")
    parser.add_argument("--vqgan_config_path", type=str, default=None,
                        help="local taming config yaml")
    parser.add_argument("--openai_enc_path", type=str, default=None,
                        help="local OpenAI dVAE encoder.pkl (downloads when omitted)")
    parser.add_argument("--openai_dec_path", type=str, default=None,
                        help="local OpenAI dVAE decoder.pkl")
    parser.add_argument("--dalle_output_file_name", type=str, default="dalle")
    parser.add_argument("--fp16", "--bf16", dest="bf16", action="store_true",
                        help="bf16 compute (the TPU-native analog of --fp16/--amp)")
    parser.add_argument("--amp", dest="bf16", action="store_true")
    parser.add_argument("--wandb", action="store_true")
    parser.add_argument("--wandb_name", default="dalle_train_transformer")
    parser.add_argument("--wandb_entity", default=None,
                        help="W&B entity (team/user) the run is logged under "
                             "(ref train_dalle.py:83)")
    parser.add_argument("--stable_softmax", action="store_true")
    parser.add_argument("--seed", type=int, default=42)

    mesh_group = parser.add_argument_group("Mesh settings")
    mesh_group.add_argument("--fsdp", type=int, default=1)
    mesh_group.add_argument("--tp", type=int, default=1)
    mesh_group.add_argument("--sp", type=int, default=1,
                            help="sequence/context parallel extent (ring + "
                                 "Ulysses attention over the sp mesh axis)")
    mesh_group.add_argument("--pp", type=int, default=1,
                            help="pipeline parallel extent (GPipe microbatch "
                                 "schedule; needs uniform attn_types and "
                                 "depth divisible by pp)")
    mesh_group.add_argument("--pp_microbatches", type=int, default=4,
                            help="GPipe microbatches per step (should divide "
                                 "the per-data-shard batch; more microbatches "
                                 "= smaller pipeline bubble)")
    mesh_group.add_argument("--ep", type=int, default=1,
                            help="expert parallel extent (shards MoE experts "
                                 "over the ep mesh axis; use with "
                                 "--moe_experts)")

    moe_group = parser.add_argument_group("Mixture-of-experts settings")
    moe_group.add_argument("--moe_experts", type=int, default=0,
                           help="number of experts per MoE feed-forward "
                                "(0 = dense FF everywhere)")
    moe_group.add_argument("--moe_every", type=int, default=2,
                           help="every n-th layer's FF becomes an MoE layer")
    moe_group.add_argument("--moe_aux_weight", type=float, default=1e-2,
                           help="weight of the Switch load-balance loss")
    moe_group.add_argument("--moe_capacity_factor", type=float, default=1.25,
                           help="per-expert token capacity multiplier; "
                                "overflow tokens fall through the residual")

    train_group = parser.add_argument_group("Training settings")
    train_group.add_argument("--epochs", default=20, type=int)
    train_group.add_argument("--save_every_n_steps", default=1000, type=int)
    train_group.add_argument("--sample_every_n_steps", default=1000, type=int)
    train_group.add_argument("--keep_n_checkpoints", default=None, type=int)
    train_group.add_argument("--batch_size", default=4, type=int)
    train_group.add_argument("--ga_steps", default=1, type=int,
                             help="gradient accumulation steps")
    train_group.add_argument("--learning_rate", default=3e-4, type=float)
    train_group.add_argument("--clip_grad_norm", default=0.5, type=float)
    train_group.add_argument("--lr_decay", action="store_true")
    train_group.add_argument("--sharded_ckpt", action="store_true",
                             help="also write orbax sharded checkpoints (multi-host scale)")
    train_group.add_argument("--no_auto_resume", dest="auto_resume",
                             action="store_false",
                             help="don't auto-resume from a verified "
                                  "<name>-cp step dir (by default a "
                                  "preempted run relaunched with the SAME "
                                  "command picks up where it stopped; a "
                                  "NEW experiment should use a fresh "
                                  "--dalle_output_file_name or this flag)")
    train_group.add_argument("--nan_abort_after", default=5, type=int,
                             help="abort after this many CONSECUTIVE "
                                  "non-finite steps (each is skipped on "
                                  "device and the batch retried; a "
                                  "persistent NaN means the run is dead)")
    train_group.add_argument("--profile_trace_dir", default=None, type=str,
                             help="capture a jax.profiler trace (viewable in "
                                  "TensorBoard/XProf) around --profile_step; "
                                  "the analog of the reference's DeepSpeed "
                                  "--flops_profiler (train_dalle.py:473-480)")
    train_group.add_argument("--profile_step", default=200, type=int,
                             help="global step at which the trace starts; it "
                                  "spans 3 steps (the reference profiles step "
                                  "200)")
    train_group.add_argument("--telemetry", action="store_true",
                             help="enable the unified telemetry layer "
                                  "(utils/telemetry.py): train.* span/"
                                  "histogram instrumentation and a JSONL "
                                  "flight recorder drained on preemption/"
                                  "exit — a crashed run leaves a postmortem "
                                  "trace. Off by default: disabled telemetry "
                                  "is a true no-op (no threads, no files)")
    train_group.add_argument("--telemetry_dir", default=None, type=str,
                             help="flight-recorder directory (default "
                                  "<dalle_output_file_name>-telemetry)")
    train_group.add_argument("--metrics_port", default=None, type=int,
                             help="with --telemetry: serve the Prometheus-"
                                  "style /metrics exposition on 127.0.0.1:"
                                  "PORT (localhost-only by design; "
                                  "docs/DESIGN.md §9)")

    model_group = parser.add_argument_group("Model settings")
    model_group.add_argument("--dim", default=512, type=int)
    model_group.add_argument("--text_seq_len", default=256, type=int)
    model_group.add_argument("--depth", default=2, type=int)
    model_group.add_argument("--heads", default=8, type=int)
    model_group.add_argument("--dim_head", default=64, type=int)
    model_group.add_argument("--ff_dropout", default=0.0, type=float)
    model_group.add_argument("--attn_dropout", default=0.0, type=float)
    model_group.add_argument("--reversible", action="store_true")
    model_group.add_argument("--remat", action="store_true",
                             help="jax.checkpoint rematerialization per block")
    model_group.add_argument("--loss_img_weight", default=7, type=int)
    model_group.add_argument("--attn_types", default="full", type=str,
                             help="comma-separated: full, sparse, axial_row, axial_col, conv_like, mlp")
    model_group.add_argument("--shift_tokens", action="store_true")
    model_group.add_argument("--rotary_emb", action="store_true")
    return parser


def parse_args():
    return build_parser().parse_args()


def pick_tokenizer(args):
    from dalle_pytorch_tpu.data import (
        ChineseTokenizer,
        HugTokenizer,
        SimpleTokenizer,
        YttmTokenizer,
    )

    if args.chinese:
        return ChineseTokenizer()
    if args.hug:
        assert args.bpe_path is not None, "--hug requires --bpe_path (tokenizer json)"
        return HugTokenizer(args.bpe_path)
    if args.bpe_path is not None:
        if args.bpe_path.endswith(".json"):
            return HugTokenizer(args.bpe_path)
        if args.bpe_path.endswith(".model"):
            return YttmTokenizer(args.bpe_path)
    return SimpleTokenizer(args.bpe_path)


def main():
    args = parse_args()

    from dalle_pytorch_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()

    from dalle_pytorch_tpu.data import DataLoader, TarImageTextDataset, TarLoader, TextImageDataset
    from dalle_pytorch_tpu.models import DALLE, DiscreteVAE, generate_images
    from dalle_pytorch_tpu.models.factory import (
        dalle_from_checkpoint,
        save_dalle_checkpoint,
        vae_from_checkpoint,
    )
    from dalle_pytorch_tpu.parallel import (
        TrainLoop,
        create_train_state,
        init_distributed,
        make_runtime,
        make_train_step,
    )
    from dalle_pytorch_tpu.utils.profiling import StepCapture
    from dalle_pytorch_tpu.utils import (
        FAULTS,
        MetricsLogger,
        PreemptionHandler,
        ReduceLROnPlateau,
        ConstantLR,
        TELEMETRY,
        Throughput,
        counters,
        latest_verified_step,
        load_sharded_checkpoint,
        save_sharded_checkpoint,
    )

    init_distributed()
    runtime = make_runtime(
        fsdp=args.fsdp, tp=args.tp, sp=args.sp, pp=args.pp, ep=args.ep
    )
    runtime.check_batch_size(args.batch_size)
    tokenizer = pick_tokenizer(args)
    dtype = jnp.bfloat16 if args.bf16 else jnp.float32

    # ---- VAE + DALLE reconstitution (resume | vae_path | error) ----------
    start_epoch = 0
    sched_state = None
    resume_params = None
    if args.dalle_path:
        dalle, resume_params, vae, vae_params, meta = dalle_from_checkpoint(
            args.dalle_path,
            vae_weight_paths={
                k: getattr(args, k)
                for k in (
                    "openai_enc_path", "openai_dec_path",
                    "vqgan_config_path", "vqgan_model_path",
                )
            },
        )
        start_epoch = int(meta.get("epoch", -1)) + 1
        sched_state = meta.get("scheduler_state")
        assert vae is not None, "resume checkpoint carries no VAE"
        # parallel layout is a runtime choice, not a model hyperparameter:
        # follow this run's --sp/--pp, not the checkpoint's
        want_sp = "sp" if args.sp > 1 else None
        want_pp = "pp" if args.pp > 1 else None
        if (
            dalle.sp_axis != want_sp
            or dalle.pp_axis != want_pp
            or dalle.pp_microbatches != args.pp_microbatches
        ):
            dalle = dalle.clone(
                sp_axis=want_sp,
                pp_axis=want_pp,
                pp_microbatches=args.pp_microbatches,
            )
    else:
        # VAE selection mirrors the reference (train_dalle.py:235-307):
        # --vae_path (self-trained) > --taming (VQGAN) > OpenAI dVAE default
        if args.vae_path:
            vae, vae_params, _ = vae_from_checkpoint(args.vae_path)
        elif args.taming:
            from dalle_pytorch_tpu.models.vqgan import load_vqgan_vae

            vae, vae_params = load_vqgan_vae(
                args.vqgan_config_path, args.vqgan_model_path, dtype=dtype
            )
        else:
            from dalle_pytorch_tpu.models.pretrained import load_openai_vae

            if runtime.is_root_worker():
                print("using OpenAI's pretrained VAE for encoding images to tokens")
            vae, vae_params = load_openai_vae(
                args.openai_enc_path, args.openai_dec_path, dtype=dtype
            )
        dalle = DALLE(
            dim=args.dim,
            depth=args.depth,
            num_text_tokens=tokenizer.vocab_size,
            text_seq_len=args.text_seq_len,
            num_image_tokens=vae.num_tokens,
            image_fmap_size=vae.fmap_size,
            heads=args.heads,
            dim_head=args.dim_head,
            reversible=args.reversible,
            attn_dropout=args.attn_dropout,
            ff_dropout=args.ff_dropout,
            attn_types=tuple(args.attn_types.split(",")),
            loss_img_weight=args.loss_img_weight,
            stable=args.stable_softmax,
            shift_tokens=args.shift_tokens,
            rotary_emb=args.rotary_emb,
            remat=args.remat,
            sp_axis="sp" if args.sp > 1 else None,
            pp_axis="pp" if args.pp > 1 else None,
            pp_microbatches=args.pp_microbatches,
            ff_experts=args.moe_experts,
            moe_every=args.moe_every,
            moe_capacity_factor=args.moe_capacity_factor,
            dtype=dtype,
        )

    # ---- data ------------------------------------------------------------
    if args.wds or args.image_text_folder.endswith(".tar"):
        wds_spec = "" if args.wds == "auto" else args.wds
        wds_cols = [c.strip() for c in wds_spec.split(",") if c.strip()]
        if wds_cols and len(wds_cols) != 2:
            raise SystemExit(
                f"--wds wants 2 comma-separated column names (img,cap); got {args.wds!r}"
            )
        dataset = TarImageTextDataset(
            args.image_text_folder,
            text_len=dalle.text_seq_len,
            image_size=vae.image_size,
            truncate_captions=args.truncate_captions,
            resize_ratio=args.resize_ratio,
            tokenizer=tokenizer,
            image_key=wds_cols[0] if len(wds_cols) == 2 else None,
            caption_key=wds_cols[1] if len(wds_cols) == 2 else None,
            process_index=runtime.process_index,
            process_count=runtime.process_count,
        )
        loader = TarLoader(dataset, args.batch_size)
    else:
        dataset = TextImageDataset(
            args.image_text_folder,
            text_len=dalle.text_seq_len,
            image_size=vae.image_size,
            truncate_captions=args.truncate_captions,
            resize_ratio=args.resize_ratio,
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
        project="dalle_train_transformer",
        run_name=args.wandb_name,
        config=vars(args),
        enabled=runtime.is_root_worker(),
        use_wandb=args.wandb,
        entity=args.wandb_entity,
    )

    if args.telemetry:
        # root-rank-guarded like MetricsLogger: one host records/exposes
        TELEMETRY.configure(
            enabled=runtime.is_root_worker(),
            flight_dir=(
                args.telemetry_dir
                or f"{args.dalle_output_file_name}-telemetry"
            ),
            metrics_port=args.metrics_port,
        )

    # ---- params / optimizer / compiled step ------------------------------
    text0 = jnp.zeros((1, dalle.text_seq_len), jnp.int32)
    image0 = jnp.zeros((1, dalle.image_seq_len), jnp.int32)
    if resume_params is not None:
        params = resume_params
    else:
        params = jax.jit(dalle.init)(jax.random.key(args.seed), text0, image0)["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    logger.log_text(
        f"DALLE {n_params:,} params | seq {dalle.total_seq_len} | "
        f"mesh {dict(runtime.mesh.shape)} | "
        f"tokenizer {type(tokenizer).__name__}"
    )

    optimizer = optax.chain(
        optax.clip_by_global_norm(args.clip_grad_norm),
        optax.scale_by_adam(),
    )
    if args.ga_steps > 1:
        optimizer = optax.MultiSteps(optimizer, every_k_schedule=args.ga_steps)
    state, shardings = create_train_state(params, optimizer, runtime)
    if args.dalle_path:
        # keep Adam moments across resume (reference restores opt_state,
        # train_dalle.py:419-426)
        from dalle_pytorch_tpu.models.factory import restore_opt_state
        from dalle_pytorch_tpu.parallel import shard_pytree

        host_opt = restore_opt_state(
            args.dalle_path, jax.tree_util.tree_map(np.asarray, state.opt_state)
        )
        if host_opt is not None:
            state = state._replace(
                opt_state=shard_pytree(host_opt, shardings.opt_state)
            )
    del params, resume_params

    vae_encode = jax.jit(
        lambda img: vae.apply(
            {"params": vae_params}, img, method="get_codebook_indices"
        ),
        out_shardings=runtime.data_sharding,
    )

    def loss_fn(p, batch, rng):
        kwargs = dict(
            return_loss=True,
            deterministic=(args.attn_dropout == 0 and args.ff_dropout == 0),
            rngs={"dropout": rng},
        )
        # gate on the MODEL (a resumed checkpoint carries ff_experts even
        # when --moe_experts was not re-specified)
        if dalle.ff_experts > 0:
            # MoE layers sow their Switch load-balance penalty into the
            # mutable moe_aux collection (ops/moe.py)
            loss, mut = dalle.apply(
                {"params": p}, batch["text"], batch["image"],
                mutable=["moe_aux"], **kwargs,
            )
            # absent when no layer is actually MoE (e.g. moe_every > depth)
            aux = sum(jax.tree_util.tree_leaves(mut.get("moe_aux", {})))
            return loss + args.moe_aux_weight * aux
        return dalle.apply(
            {"params": p}, batch["text"], batch["image"], **kwargs
        )

    step_fn = make_train_step(
        loss_fn, optimizer, runtime, shardings, dynamic_lr=True,
        # nan_at_step is the fault-harness hook (utils/faults.py): forces
        # one NaN loss at step K inside the jitted step; None in production
        nan_inject_step=FAULTS.value("nan_at_step"),
    )

    sched = (
        ReduceLROnPlateau(args.learning_rate)
        if args.lr_decay
        else ConstantLR(args.learning_rate)
    )
    if sched_state:
        sched.load_state_dict(sched_state)
    lr = sched.lr

    ckpt_path = f"{args.dalle_output_file_name}.ckpt"
    sharded_dir = f"{args.dalle_output_file_name}-cp"

    # ---- step-granular resume (preemption recovery) ----------------------
    # A verified step dir under <name>-cp (periodic --sharded_ckpt save or a
    # previous run's emergency save) resumes params+opt+step exactly where
    # the preempted run stopped — load_sharded_checkpoint skips torn/corrupt
    # dirs and falls back to the newest verified one.
    resume_epoch = resume_iter = -1
    global_step = 0
    verified = None
    if args.auto_resume:
        # probe (full checksum pass) on one host; N hosts hashing the same
        # multi-GB dir on shared storage would multiply relaunch I/O
        if jax.process_index() == 0:
            verified = latest_verified_step(sharded_dir)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            verified = int(multihost_utils.broadcast_one_to_all(
                np.int32(-1 if verified is None else verified)
            ))
            verified = None if verified < 0 else verified
    if verified is not None:
        # state itself is the shape/dtype template — the shardings path
        # never reads values, and np.asarray-ing a pod-sharded state would
        # gather (or crash on non-addressable shards). verify=False: the
        # probe just hashed this exact dir
        state, smeta, global_step = load_sharded_checkpoint(
            sharded_dir, state, step=verified, shardings=shardings,
            verify=False,
        )
        resume_epoch = int(smeta.get("epoch", -1))
        resume_iter = int(smeta.get("iter", -1))
        if smeta.get("scheduler_state"):
            sched.load_state_dict(smeta["scheduler_state"])
            lr = sched.lr
        if resume_epoch >= 0:
            start_epoch = resume_epoch
        logger.log_text(
            f"resuming from {sharded_dir} step {global_step} "
            f"(epoch {resume_epoch}, iter {resume_iter})"
        )
        # batch-skip replay needs a loader whose per-epoch order is
        # reproducible in a fresh process (the folder DataLoader reshuffles
        # from seed+epoch). Tar streams advance a sequential rng across
        # epochs, so skipping indices would drop/duplicate samples — replay
        # the partial epoch from its start instead (duplication is the safe
        # direction) and say so.
        if resume_iter >= 0 and not hasattr(loader, "epoch"):
            logger.log_text(
                "tar-stream loader has no reproducible epoch order: "
                f"replaying epoch {resume_epoch} from its start "
                f"(up to {resume_iter + 1} batches re-seen)"
            )
            resume_iter = -1

    def save(epoch):
        # gather is a collective — every process participates; only the
        # root writes the file
        with TELEMETRY.span("train.ckpt_save", kind="full", epoch=epoch):
            host_params = runtime.to_host(loop.state.params)
            host_opt = runtime.to_host(loop.state.opt_state)
            if not runtime.is_root_worker():
                return
            save_dalle_checkpoint(
                ckpt_path, dalle, host_params, vae, vae_params,
                extra={"epoch": epoch, "scheduler_state": sched.state_dict()},
                opt_state=host_opt, step=int(loop.state.step),
            )

    def save_sharded(epoch, it, emergency=False):
        # step-granular, verified (manifest + commit marker): the resume
        # probe above restores exactly this. Collective — every host writes
        # its addressable shards. int(state.step) = dispatched attempts:
        # resume numbers its next step correctly
        step = int(loop.state.step)
        with TELEMETRY.span(
            "train.ckpt_save", kind="sharded", step=step,
            emergency=emergency,
        ):
            save_sharded_checkpoint(
                sharded_dir, step, loop.state,
                meta={
                    "epoch": epoch, "iter": it,
                    "scheduler_state": sched.state_dict(),
                    "emergency": emergency,
                },
                keep_n=args.keep_n_checkpoints,
            )

    throughput = Throughput(window=10)
    capture = StepCapture(
        args.profile_trace_dir if runtime.is_root_worker() else None,
        args.profile_step,
    )

    def feed(batch):
        image_tokens = vae_encode(batch["image"])
        train_batch = {
            "text": jnp.asarray(batch["text"]),
            "image": image_tokens,
        }
        # a steady-state window of three steps; the loop's lexical
        # TELEMETRY spans land in the same capture (utils/profiling.py)
        if capture.at_step(loop.global_step, loop.state.params):
            logger.log_text(
                f"profiler trace for steps "
                f"{args.profile_step}..{args.profile_step + 2} "
                f"written to {args.profile_trace_dir}"
            )
        return train_batch

    def on_nan_abort(it):
        # the rejected batch's update is NOT in state: ``it`` is its
        # predecessor, so a later resume replays it
        save_sharded(epoch, it, emergency=True)
        logger.finish()
        return f"state saved for post-mortem at {sharded_dir}"

    # the dispatch/verdict/retry policy (parallel/loop.py); sched.step sees
    # the loss of every applied step
    loop = TrainLoop(
        step_fn, state, feed=feed, lr=lr, on_applied=sched.step,
        nan_abort_after=args.nan_abort_after, log=logger.log_text,
        on_abort=on_nan_abort, global_step=global_step,
        resume=(resume_epoch, resume_iter),
    )
    del state  # donated by the first step: loop.state is the live one

    # pre-flight save: fail early when misconfigured (train_dalle.py:561-563)
    save(start_epoch - 1)

    def on_preempt_signal(signum):
        # flight recorder to disk INSIDE the signal handler: even if the
        # in-flight step or the emergency save below hangs, the run's last
        # seconds are already on disk (fail-open; utils/telemetry.py)
        TELEMETRY.event("train.preempt_signal", signum=signum,
                        step=loop.global_step)
        TELEMETRY.drain("preempt_signal")

    with PreemptionHandler(on_signal=on_preempt_signal) as preempt:
        for epoch in range(start_epoch, args.epochs):
            if hasattr(loader, "epoch"):
                loader.epoch = epoch  # keep shuffle order aligned on resume
            for i, train_batch, loss in loop.epoch(epoch, loader):
                global_step = loop.global_step
                if global_step % 10 == 0:
                    logger.log(
                        {"loss": float(loss), "epoch": epoch, "iter": i,
                         "lr": loop.lr, "nan_skips": counters.get("train.nan_skips")},
                        step=global_step,
                    )
                if global_step % 100 == 0:
                    # data-path fault accounting
                    logger.log_counters(step=global_step, prefix="webdata.")
                    logger.log_counters(step=global_step, prefix="download.")
                rate = throughput.update(args.batch_size)
                if rate is not None:
                    logger.log({"sample_per_sec": rate}, step=global_step)

                if global_step > 0 and global_step % args.save_every_n_steps == 0:
                    # resolve the in-flight step first: the saved scheduler
                    # state must include its loss, and a device-rejected
                    # batch is absent from the saved state, so resume must
                    # replay it
                    it = loop.resolve()
                    save(epoch)
                    if args.sharded_ckpt:
                        save_sharded(epoch, it)

                if global_step > 0 and global_step % args.sample_every_n_steps == 0:
                    # sampling over sharded params is collective: all
                    # processes run it; only the root writes the image
                    images = generate_images(
                        dalle, loop.state.params, vae, {"params": vae_params},
                        train_batch["text"][:1], jax.random.key(global_step),
                    )
                    if runtime.is_root_worker():
                        from PIL import Image

                        from dalle_pytorch_tpu.models.vae import denormalize

                        out = Path("dalle_samples")
                        out.mkdir(exist_ok=True)
                        pix = denormalize(images, getattr(vae, "normalization", None))
                        arr = (pix[0] * 255).astype(np.uint8)
                        Image.fromarray(arr).save(out / f"sample_{global_step:07d}.png")
                        logger.log_images("samples", pix, step=global_step)

                if preempt.triggered:
                    # SIGTERM/SIGINT (pod preemption): write the emergency
                    # step-granular checkpoint and exit cleanly; the next
                    # launch resumes from it via the startup probe. As with
                    # periodic saves, the in-flight step's verdict is
                    # resolved first, so scheduler state is complete and a
                    # just-rejected batch is recorded as unconsumed (the
                    # relaunch must replay it)
                    capture.close()
                    save_sharded(epoch, loop.resolve(), emergency=True)
                    logger.log_text(
                        f"emergency checkpoint at step {global_step + 1} "
                        f"(epoch {epoch}, iter {i}) written to {sharded_dir}; "
                        "exiting"
                    )
                    logger.finish()
                    sys.exit(0)

            save(epoch)
            if args.sharded_ckpt:
                # epoch fully consumed: a resume starts at the NEXT epoch
                save_sharded(epoch + 1, -1)
            # per-epoch model artifact (reference train_dalle.py:637-649);
            # the logger is already root-gated via enabled=
            logger.log_artifact("trained-dalle", ckpt_path, metadata=vars(args))
            logger.log_text(f"epoch {epoch} complete")

    capture.close(loop.state.params)  # training ended inside the trace window

    logger.finish()


if __name__ == "__main__":
    main()
