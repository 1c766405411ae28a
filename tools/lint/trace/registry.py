"""The repo's trace-audit entry points.

This module — unlike the AST stage — IMPORTS the package, because its
job is to enumerate the (shape, dtype, static-arg) signatures the real
code paths can feed each hot jit. Everything is derived from the same
objects production uses:

* the serving jits' signatures come from ``EngineConfig``/model config
  exactly the way ``serving/engine.py`` computes them (chunk widths via
  the engine's own ``_next_chunk``, the top-k ``k`` via the engine's
  formula, cache avals via ``init_decode_cache``/``set_decode_offsets``
  under ``jax.eval_shape``),
* the train entry builds a real ``make_train_step`` (donated state,
  NaN guard on) over a single-device mesh,
* the sampling entry traces ``generate_image_tokens`` end to end.

All avals are abstract (``jax.eval_shape`` — no device execution, no
compilation), over a CANONICAL small config: byte budgets in the
contract are for this config, and what the audit guards is the *shape*
of the program (signature count, donation aliasing, readbacks, relative
footprint), which is config-independent. Changing the canonical config
is an intentional contract change — re-emit with
``python tools/lint.py --trace --emit-contract``.

Adding an entry point: build its abstract args here, declare its donated
args, list every signature the surrounding code can produce, and append
an ``EntryPoint``; then re-emit the contract and commit both (see
docs/DESIGN.md §11).
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import List

# absolute import: this module is loaded by FILE PATH (audit._load_registry,
# same mechanism fixture registries use), so it has no parent package
from lint.trace.types import EntryPoint, Signature

# the canonical audit model: tiny (trace cost, not fidelity, scales with
# size) but structurally the production shape — rotary, full attention,
# the same layer stack the serving gates drive (tools/serve_smoke.py)
CANON_MODEL = dict(
    dim=32, depth=2, num_text_tokens=16, text_seq_len=4,
    num_image_tokens=12, image_fmap_size=2, heads=2, dim_head=8,
    attn_types=("full",), rotary_emb=True,
)
# the canonical engine: chunked prefill on, the production serving shape
CANON_ENGINE = dict(max_batch=2, prefill_chunk=2)

# the canonical post-decode stage models (serving/postdecode.py): a VAE
# whose token space and image_seq_len MATCH the canonical DALLE
# (num_tokens == num_image_tokens, fmap == image_fmap_size, so the
# engine's generated ids are valid decode input), and a CLIP sized to
# the canonical text vocab/seq — the same tiny pair the serve-smoke
# stage drill builds
CANON_VAE = dict(
    image_size=4, num_layers=1, num_tokens=12, codebook_dim=16,
    hidden_dim=8,
)
CANON_CLIP = dict(
    dim_text=16, dim_image=16, dim_latent=16, num_text_tokens=16,
    text_enc_depth=1, text_seq_len=4, text_heads=2, text_dim_head=8,
    num_visual_tokens=12, visual_enc_depth=1, visual_heads=2,
    visual_dim_head=8, visual_image_size=4, visual_patch_size=2,
)


def build_entry_points() -> List[EntryPoint]:
    import os

    import jax
    import jax.numpy as jnp

    # Pin the KV page size for this PROCESS (aval derivation here AND the
    # audit traces that follow): tests override DALLE_TPU_KV_PAGE_SIZE to
    # exercise page-boundary arithmetic on tiny models, and the smoke
    # gates' lint pre-flight subprocesses inherit that env — but the
    # committed contract describes the canonical program, so its cache
    # shapes must not drift with the caller's environment.
    from dalle_pytorch_tpu.ops.kv_policy import DEFAULT_PAGE_SIZE

    os.environ["DALLE_TPU_KV_PAGE_SIZE"] = str(DEFAULT_PAGE_SIZE)

    from dalle_pytorch_tpu.models import DALLE
    from dalle_pytorch_tpu.models.sampling import (
        generate_image_tokens,
        init_decode_cache,
        set_decode_offsets,
    )
    from dalle_pytorch_tpu.serving import engine as eng
    from dalle_pytorch_tpu.serving.engine import Engine, EngineConfig

    SDS = jax.ShapeDtypeStruct
    dalle = DALLE(**CANON_MODEL)
    cfg = EngineConfig(**CANON_ENGINE)
    B = cfg.max_batch
    T = dalle.text_len_internal

    text1 = SDS((1, dalle.text_seq_len), jnp.int32)
    image1 = SDS((1, dalle.image_seq_len), jnp.int32)
    params = jax.eval_shape(
        lambda t, i: dalle.init(jax.random.key(0), t, i), text1, image1
    )["params"]
    internal = jax.eval_shape(dalle.remap_text, text1)  # (1, T) with bos

    def cache_avals(b, kv_quant=None):
        def build(p):
            return set_decode_offsets(
                init_decode_cache(
                    dalle, p, b, cache_format="paged", kv_quant=kv_quant
                ),
                jnp.zeros((b,), jnp.int32),
            )
        return jax.eval_shape(build, params)

    cache1 = cache_avals(1)
    cacheB = cache_avals(B)
    # the quantized-KV engine (ops/kv_policy.py kv_quant="int8"): int8
    # content pools + parallel f32 scale pools — the cache aval change
    # behind EngineConfig.kv_quant, derived through the engine's own
    # init path so the committed contract (and its DTL141 byte budget,
    # the standing guard that quantized KV stays roughly half-size)
    # tracks the code
    cacheB_q = cache_avals(B, kv_quant="int8")
    key = jax.eval_shape(lambda: jax.random.key(0))
    keysB = jax.eval_shape(lambda: jnp.stack([jax.random.key(0)] * B))
    # the engine's own top-k formula (Engine.__init__: full-vocab-derived
    # fractional k over the image-only head)
    k_img = max(int((1 - cfg.filter_thres) * dalle.total_tokens), 1)
    i32 = SDS((), jnp.int32)

    # the prefix-cache engine variant (serving/prefix_cache.py): arena
    # rows appended to the BATCHED pools only — the one config knob that
    # changes a serving-jit cache aval. Arena sizing mirrors
    # Engine.__init__ exactly, via the engine's own helpers, so the
    # committed contract tracks the code, not a transcription of it.
    from dalle_pytorch_tpu.ops import kv_policy
    from dalle_pytorch_tpu.serving.engine import (
        _append_arena_rows, arena_rows_for,
    )
    from dalle_pytorch_tpu.serving.scheduler import pages_for

    page = kv_policy.page_size()
    n_pages_slot = pages_for(T + dalle.image_seq_len, page)
    arena_rows = arena_rows_for(None, pages_for(T, page), n_pages_slot)
    cacheB_arena = jax.eval_shape(
        lambda c: _append_arena_rows(c, arena_rows), cacheB
    )
    # quantized prefix engine: arena rows appended to the int8 + scale
    # pools — the publish/COW/restore copy jits run over this tree
    cacheB_q_arena = jax.eval_shape(
        lambda c: _append_arena_rows(c, arena_rows), cacheB_q
    )
    cache1_q = cache_avals(1, kv_quant="int8")
    # the cached terminal logits (the full-hit payload): the prefill
    # jits' third output, derived abstractly from the same trace
    logits1 = jax.eval_shape(
        lambda p, c, i, k: eng._prefill_jit.__wrapped__(
            dalle, p, c, i, k, k_img, 1.0
        ),
        params, cache1, internal, key,
    )[2]

    # the speculative fused engine (ROADMAP 2): the SAME checkpoint with
    # the token-shift ring widened by spec_k rows (the rollback slack) and
    # the block width stretched to carry a full verify row — both derived
    # through the engine's OWN helpers (spec_model / fused_width) so the
    # committed contract tracks the code, not a transcription of it
    from dalle_pytorch_tpu.serving.engine import fused_width, spec_model

    cfg_spec = EngineConfig(
        **CANON_ENGINE, fused_iteration=True, spec_decode=True,
    )
    dalle_spec = spec_model(dalle, cfg_spec.spec_k)
    W_spec = fused_width(cfg_spec)

    def cache_avals_for(model, b):
        def build(p):
            return set_decode_offsets(
                init_decode_cache(model, p, b, cache_format="paged"),
                jnp.zeros((b,), jnp.int32),
            )
        return jax.eval_shape(build, params)

    cacheB_spec = cache_avals_for(dalle_spec, B)
    # spec + prefix-cache composition: arena rows appended to the
    # ring-widened batched pools — page counts are seq-len-derived, so
    # the arena sizing is identical to the plain prefix engine's
    cacheB_spec_arena = jax.eval_shape(
        lambda c: _append_arena_rows(c, arena_rows), cacheB_spec
    )
    # per-slot BASE sampling keys (Engine._base_keys): the spec jit
    # derives the whole (B, W) key matrix from these in-trace
    keysB_base = jax.eval_shape(
        lambda: jnp.stack([jax.random.key(0)] * B)
    )
    # the donated fixed-shape page-copy jits (the PR 10 follow-on): call
    # vectors pad to the engine's copy width — at most one prompt's pages
    # (Engine.__init__: self._copy_pad)
    copy_pad = pages_for(T, page)
    copy_vec = SDS((copy_pad,), jnp.int32)

    # the donated prefix-map jit (this PR's follow-on closing the PR 10
    # set): its ids vector pads to the page-table ROW width, and the
    # shift-ring seam arrives as a keystr-keyed dict of row avals derived
    # from the cache tree itself — the same dict shape every admission
    # call builds from a prefix node's ring
    map_ids = SDS((n_pages_slot,), jnp.int32)

    def ring_avals(cache):
        rows = {}

        def fn(path, x):
            if getattr(path[-1], "key", None) == "shift_hist":
                rows[jax.tree_util.keystr(path)] = SDS(x.shape[1:], x.dtype)
            return x

        jax.tree_util.tree_map_with_path(fn, cache)
        return rows

    # chunk widths exactly as the engine schedules them: simulate the
    # REAL Engine._next_chunk (1-token tails merged) over (T, chunk)
    shim = SimpleNamespace(config=cfg, T=T)
    widths, filled = [], 0
    while filled < T:
        c = Engine._next_chunk(shim, filled)
        widths.append((c, filled + c >= T))
        filled += c
    chunk_widths = sorted({c for c, final in widths if not final})
    final_widths = sorted({c for c, final in widths if final})

    entries = [
        EntryPoint(
            name="serving.prefill",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_prefill_jit",
            fn=eng._prefill_jit,
            lower=eng._prefill_jit.lower,
            static_argnums=(0, 5),
            donate={"cache": 2},
            signatures=[Signature(
                "monolithic",
                (dalle, params, cache1, internal, key, k_img, 1.0),
            )],
        ),
        EntryPoint(
            name="serving.prefill_chunk",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_prefill_chunk_jit",
            fn=eng._prefill_chunk_jit,
            lower=eng._prefill_chunk_jit.lower,
            static_argnums=(0,),
            donate={"cache": 2},
            signatures=[
                Signature(
                    f"chunk_w{c}",
                    (dalle, params, cache1, SDS((1, c), jnp.int32), i32),
                )
                for c in chunk_widths
            ],
        ),
        EntryPoint(
            name="serving.prefill_last",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_prefill_last_jit",
            fn=eng._prefill_last_jit,
            lower=eng._prefill_last_jit.lower,
            static_argnums=(0, 5),
            donate={"cache": 2},
            signatures=[
                Signature(
                    f"final_w{c}",
                    (dalle, params, cache1, SDS((1, c), jnp.int32), i32,
                     k_img, key, 1.0),
                )
                for c in final_widths
            ],
        ),
        EntryPoint(
            name="serving.iteration",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_iteration_jit",
            fn=eng._iteration_jit,
            lower=eng._iteration_jit.lower,
            static_argnums=(0, 9, 10, 12),
            donate={"cache": 2},
            # the fused ragged iteration: descriptor raggedness is DATA,
            # so every steady prefill/decode mix is EXACTLY the "steady"
            # signature; "final" is the one additional class (iterations
            # containing a FINAL chunk run the per-row split-parity
            # heads — any_final is a host-known static). Both compile at
            # warmup; anything beyond these two is the
            # shape-drift-recompile bug class
            signatures=[
                Signature(
                    "steady",
                    (dalle, params, cacheB, SDS((B, T), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.bool_), keysB,
                     cfg.prefill_chunk, k_img, 1.0, False),
                ),
                Signature(
                    "final",
                    (dalle, params, cacheB, SDS((B, T), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.bool_), keysB,
                     cfg.prefill_chunk, k_img, 1.0, True),
                ),
            ],
        ),
        EntryPoint(
            name="serving.decode",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_decode_jit",
            fn=eng._decode_jit,
            lower=eng._decode_jit.lower,
            static_argnums=(0, 6),
            donate={"cache": 2},
            # steady state is EXACTLY one signature: the engine always
            # dispatches the full max_batch width with vectorized
            # positions/keys — any second signature here is the
            # batch-shape recompile bug class this audit exists to catch
            signatures=[Signature(
                "steady",
                (dalle, params, cacheB, SDS((B,), jnp.int32),
                 SDS((B,), jnp.int32), keysB, k_img, 1.0),
            )],
        ),
        EntryPoint(
            name="serving.iteration_prefix",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_iteration_jit",
            fn=eng._iteration_jit,
            lower=eng._iteration_jit.lower,
            static_argnums=(0, 9, 10, 12),
            donate={"cache": 2},
            # the prefix-cache engine's fused pair: the SAME program
            # logic over the arena-extended batched cache (extra storage
            # rows are content-only — tables/descriptors keep the B-wide
            # shape, so the signature count stays exactly two)
            signatures=[
                Signature(
                    "steady_arena",
                    (dalle, params, cacheB_arena, SDS((B, T), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.bool_), keysB,
                     cfg.prefill_chunk, k_img, 1.0, False),
                ),
                Signature(
                    "final_arena",
                    (dalle, params, cacheB_arena, SDS((B, T), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.bool_), keysB,
                     cfg.prefill_chunk, k_img, 1.0, True),
                ),
            ],
        ),
        EntryPoint(
            name="serving.decode_prefix",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_decode_jit",
            fn=eng._decode_jit,
            lower=eng._decode_jit.lower,
            static_argnums=(0, 6),
            donate={"cache": 2},
            # prefix-cache split engine: decode over the arena-extended
            # cache — still EXACTLY one steady signature
            signatures=[Signature(
                "steady_arena",
                (dalle, params, cacheB_arena, SDS((B,), jnp.int32),
                 SDS((B,), jnp.int32), keysB, k_img, 1.0),
            )],
        ),
        EntryPoint(
            name="serving.decode_quant",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_decode_jit",
            fn=eng._decode_jit,
            lower=eng._decode_jit.lower,
            static_argnums=(0, 6),
            donate={"cache": 2},
            # the quantized-KV engine's decode: the SAME program logic
            # over int8 pools + scale pools — still EXACTLY one steady
            # signature, at roughly half the cache bytes (the DTL141
            # budget difference vs serving.decode IS the capacity claim)
            signatures=[Signature(
                "steady_quant",
                (dalle, params, cacheB_q, SDS((B,), jnp.int32),
                 SDS((B,), jnp.int32), keysB, k_img, 1.0),
            )],
        ),
        EntryPoint(
            name="serving.iteration_quant",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_iteration_jit",
            fn=eng._iteration_jit,
            lower=eng._iteration_jit.lower,
            static_argnums=(0, 9, 10, 12),
            donate={"cache": 2},
            # the quantized fused iteration: quantize-at-append +
            # in-kernel dequant are in-trace data ops, so the signature
            # budget stays the same steady/final pair as
            # serving.iteration — a third signature is the same
            # shape-drift-recompile bug class
            signatures=[
                Signature(
                    "steady_quant",
                    (dalle, params, cacheB_q, SDS((B, T), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.bool_), keysB,
                     cfg.prefill_chunk, k_img, 1.0, False),
                ),
                Signature(
                    "final_quant",
                    (dalle, params, cacheB_q, SDS((B, T), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.bool_), keysB,
                     cfg.prefill_chunk, k_img, 1.0, True),
                ),
            ],
        ),
        EntryPoint(
            name="serving.sample_cached",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_sample_cached_jit",
            fn=eng._sample_cached_jit,
            lower=eng._sample_cached_jit.lower,
            static_argnums=(2,),
            donate={},
            # the full-prefix-hit first token: top-k + categorical over
            # the CACHED terminal logits — the only program a full hit
            # dispatches before entering decode
            signatures=[Signature(
                "hit", (logits1, key, k_img, 1.0),
            )],
        ),
        EntryPoint(
            name="serving.iteration_spec",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_spec_iteration_jit",
            fn=eng._spec_iteration_jit,
            lower=eng._spec_iteration_jit.lower,
            static_argnums=(0, 9, 10, 12, 13, 14),
            donate={"cache": 2},
            # the speculative fused iteration (ROADMAP 2): draft, verify,
            # and accept in ONE dispatch over the ring-widened model.
            # Descriptor raggedness (verify widths 1..spec_k+1, chunk
            # mixes, the spec_verify_abort plain-decode fallback) is all
            # DATA, so the steady state is EXACTLY the "steady" signature
            # plus the warm "final" class (any_final) — the same
            # two-signature budget as serving.iteration; a third
            # signature is the shape-drift-recompile bug class
            signatures=[
                Signature(
                    "steady",
                    (dalle_spec, params, cacheB_spec,
                     SDS((B, T), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.bool_), keysB_base, W_spec, k_img,
                     1.0, False, cfg_spec.spec_k,
                     cfg_spec.spec_draft_depth),
                ),
                Signature(
                    "final",
                    (dalle_spec, params, cacheB_spec,
                     SDS((B, T), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.bool_), keysB_base, W_spec, k_img,
                     1.0, True, cfg_spec.spec_k,
                     cfg_spec.spec_draft_depth),
                ),
            ],
        ),
        EntryPoint(
            name="serving.iteration_spec_prefix",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_spec_iteration_jit",
            fn=eng._spec_iteration_jit,
            lower=eng._spec_iteration_jit.lower,
            static_argnums=(0, 9, 10, 12, 13, 14),
            donate={"cache": 2},
            # the spec engine with the prefix cache on: the SAME program
            # over the arena-extended, ring-widened cache — the same
            # two-signature budget (the serving.iteration_prefix pattern)
            signatures=[
                Signature(
                    "steady_arena",
                    (dalle_spec, params, cacheB_spec_arena,
                     SDS((B, T), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.bool_), keysB_base, W_spec, k_img,
                     1.0, False, cfg_spec.spec_k,
                     cfg_spec.spec_draft_depth),
                ),
                Signature(
                    "final_arena",
                    (dalle_spec, params, cacheB_spec_arena,
                     SDS((B, T), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.int32), SDS((B,), jnp.int32),
                     SDS((B,), jnp.bool_), keysB_base, W_spec, k_img,
                     1.0, True, cfg_spec.spec_k,
                     cfg_spec.spec_draft_depth),
                ),
            ],
        ),
        EntryPoint(
            name="serving.page_copy",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_copy_pages_jit",
            fn=eng._copy_pages_jit,
            lower=eng._copy_pages_jit.lower,
            static_argnums=(),
            donate={"cache": 0},
            # the donated fixed-shape publish/COW page copy (the PR 10
            # follow-on): every call pads its src/dst/valid vectors to
            # the engine's copy width, so ONE signature per cache tree
            # covers publish, map-time COW, and every partial batch —
            # the eager pool-sized .at[].set rewrites this retired
            # stayed on the host path and re-traced per shape. The
            # speculative prefix engine publishes through the same jit
            # over the ring-widened arena tree: its one extra signature
            # is contracted here (the serving.iteration_spec_prefix
            # composition)
            signatures=[
                Signature(
                    "publish", (cacheB_arena, copy_vec, copy_vec, copy_vec),
                ),
                Signature(
                    "publish_spec",
                    (cacheB_spec_arena, copy_vec, copy_vec, copy_vec),
                ),
            ],
        ),
        EntryPoint(
            name="serving.page_copy_quant",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_copy_pages_jit",
            fn=eng._copy_pages_jit,
            lower=eng._copy_pages_jit.lower,
            static_argnums=(),
            donate={"cache": 0},
            # the quantized prefix engine's publish/COW copies (int8 +
            # scale pools). Its OWN entry, not a third serving.page_copy
            # signature: the audit lowers and alias-audits signature 0
            # only and reuses that count for later signatures, so a
            # tree with 4 extra scale leaves under the shared entry
            # would read as 4 host-visible outputs (loosening the
            # budget to 4 for the unquantized path too). As signature 0
            # here it is genuinely lowered: every leaf must alias into
            # the donated cache, keeping BOTH entries at the 0
            # host-visible budget.
            signatures=[Signature(
                "publish_quant",
                (cacheB_q_arena, copy_vec, copy_vec, copy_vec),
            )],
        ),
        EntryPoint(
            name="serving.page_copy_across",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_copy_pages_across_jit",
            fn=eng._copy_pages_across_jit,
            lower=eng._copy_pages_across_jit.lower,
            static_argnums=(),
            donate={"dst_cache": 0},
            # the split engine's partial-hit restore: arena pages out of
            # the batched pools into a private batch-1 prefill cache,
            # destination donated, same padded shape
            signatures=[Signature(
                "restore",
                (cache1, cacheB_arena, copy_vec, copy_vec, copy_vec),
            )],
        ),
        EntryPoint(
            name="serving.prefix_map",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_map_prefix_jit",
            fn=eng._map_prefix_jit,
            lower=eng._map_prefix_jit.lower,
            static_argnums=(),
            donate={"cache": 0},
            # the donated prefix-hit publish/map (the last PR 10 follow-on):
            # page-table row, cache/shift indices, and shift-ring seam land
            # in ONE fixed-shape dispatch — one signature per cache tree it
            # mutates: the fused/full-hit map over the batched arena tree,
            # the split engine's batch-1 seeding (n_ids == 0), and the spec
            # engine's composition over the ring-widened arena tree
            signatures=[
                Signature(
                    "map_batched",
                    (cacheB_arena, i32, map_ids, i32, i32,
                     ring_avals(cacheB_arena)),
                ),
                Signature(
                    "seed_split",
                    (cache1, i32, map_ids, i32, i32, ring_avals(cache1)),
                ),
                Signature(
                    "map_spec",
                    (cacheB_spec_arena, i32, map_ids, i32, i32,
                     ring_avals(cacheB_spec_arena)),
                ),
            ],
        ),
        EntryPoint(
            name="serving.prefix_map_quant",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_map_prefix_jit",
            fn=eng._map_prefix_jit,
            lower=eng._map_prefix_jit.lower,
            static_argnums=(),
            donate={"cache": 0},
            # quantized prefix engine's map/seed — own entry for the same
            # signature-0 aliasing-audit reason as serving.page_copy_quant
            signatures=[
                Signature(
                    "map_quant",
                    (cacheB_q_arena, i32, map_ids, i32, i32,
                     ring_avals(cacheB_q_arena)),
                ),
                Signature(
                    "seed_split_quant",
                    (cache1_q, i32, map_ids, i32, i32, ring_avals(cache1_q)),
                ),
            ],
        ),
        EntryPoint(
            name="serving.page_copy_across_quant",
            path="dalle_pytorch_tpu/serving/engine.py",
            symbol="_copy_pages_across_jit",
            fn=eng._copy_pages_across_jit,
            lower=eng._copy_pages_across_jit.lower,
            static_argnums=(),
            donate={"dst_cache": 0},
            # quantized split-engine partial-hit restore — own entry for
            # the same signature-0 aliasing-audit reason as
            # serving.page_copy_quant
            signatures=[Signature(
                "restore_quant",
                (cache1_q, cacheB_q_arena, copy_vec, copy_vec, copy_vec),
            )],
        ),
        *_stage_entries(),
        _train_entry(dalle, B),
        _block_sparse_entry(dalle, T),
        EntryPoint(
            name="sampling.generate",
            path="dalle_pytorch_tpu/models/sampling.py",
            symbol="generate_image_tokens",
            fn=lambda p, t, k: generate_image_tokens(dalle, p, t, k),
            lower=None,
            static_argnums=(),
            donate={},
            signatures=[Signature(
                "batch1", (params, text1, key),
            )],
        ),
    ]
    return entries


def _stage_entries() -> List[EntryPoint]:
    """The post-decode stage jits (serving/postdecode.py, DESIGN.md §8.5):
    batched fixed-shape VAE decode and CLIP rerank. The pipeline pads
    every dispatch to its configured batch width (StageConfig.batch ==
    the canonical engine's max_batch), so each jit has EXACTLY one
    steady signature — a second signature is the shape-drift-recompile
    bug class. VAE params are the decode-scope tree
    (``init(..., method="decode")``): the pipeline's contract is token
    ids -> pixels, so the encoder never rides along. No donation: stage tensors are tiny relative to
    the KV pools, and the image must survive the dispatch (it is the
    journal payload and the degraded-completion partial)."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.clip import CLIP
    from dalle_pytorch_tpu.models.vae import DiscreteVAE
    from dalle_pytorch_tpu.serving import postdecode as pd

    SDS = jax.ShapeDtypeStruct
    S = CANON_ENGINE["max_batch"]  # == StageConfig default batch
    vae = DiscreteVAE(**CANON_VAE)
    clip = CLIP(**CANON_CLIP)
    img_seq = SDS((1, vae.image_seq_len), jnp.int32)
    vae_params = jax.eval_shape(
        lambda i: vae.init(jax.random.key(0), i, method="decode"), img_seq
    )["params"]
    text1 = SDS((1, clip.text_seq_len), jnp.int32)
    pix1 = SDS((1, vae.image_size, vae.image_size, vae.channels),
               jnp.float32)
    clip_params = jax.eval_shape(
        lambda t, i: clip.init(jax.random.key(0), t, i), text1, pix1
    )["params"]
    return [
        EntryPoint(
            name="serving.vae_decode",
            path="dalle_pytorch_tpu/serving/postdecode.py",
            symbol="_vae_decode_jit",
            fn=pd._vae_decode_jit,
            lower=pd._vae_decode_jit.lower,
            static_argnums=(0,),
            donate={},
            signatures=[Signature(
                "steady",
                (vae, vae_params, SDS((S, vae.image_seq_len), jnp.int32)),
            )],
        ),
        EntryPoint(
            name="serving.clip_rerank",
            path="dalle_pytorch_tpu/serving/postdecode.py",
            symbol="_clip_rerank_jit",
            fn=pd._clip_rerank_jit,
            lower=pd._clip_rerank_jit.lower,
            static_argnums=(0,),
            donate={},
            # images arrive at the VAE's output size; the in-trace
            # bilinear resize to the CLIP patch grid is data, not shape
            signatures=[Signature(
                "steady",
                (clip, clip_params, SDS((S, clip.text_seq_len), jnp.int32),
                 SDS((S, vae.image_size, vae.image_size, vae.channels),
                     jnp.float32)),
            )],
        ),
    ]


def _block_sparse_entry(dalle, T: int) -> EntryPoint:
    """The pair-grid block-sparse attention kernel
    (ops/block_sparse_attention.py) over a canonical axial layout at the
    audit model's internal sequence — the jit the sparse training/prefill
    paths route through behind DALLE_TPU_SPARSE_KERNEL. Abstract trace
    only (lower=None): Pallas calls abstract-eval fine, and the audit
    guards the program shape (signatures, no readbacks), while the
    numerical contract lives in tests/test_block_sparse.py's interpret
    parity tier."""
    import jax
    import jax.numpy as jnp

    from dalle_pytorch_tpu.ops import block_sparse_attention as bs
    from dalle_pytorch_tpu.ops import masks as masks_lib

    SDS = jax.ShapeDtypeStruct
    n = T + dalle.image_seq_len
    layout = bs.compile_block_layout(
        masks_lib.axial_mask(T, dalle.image_fmap_size, axis=0)[:n, :n], 4, 4
    )
    fn = jax.jit(
        lambda q, k, v: bs.block_sparse_attention(
            q, k, v, layout, interpret=True
        )
    )
    qkv = SDS((1, dalle.heads, n, dalle.dim_head), jnp.float32)
    return EntryPoint(
        name="ops.block_sparse",
        path="dalle_pytorch_tpu/ops/block_sparse_attention.py",
        symbol="block_sparse_attention",
        fn=fn,
        lower=None,
        static_argnums=(),
        donate={},
        signatures=[Signature("axial", (qkv, qkv, qkv))],
    )


def _train_entry(dalle, batch: int) -> EntryPoint:
    """A real ``make_train_step`` (donate=True, nan_guard=True) over a
    single-device mesh, with the canonical model's own weighted-CE loss
    — auditing the builder everything in train_dalle.py runs through."""
    import jax
    import jax.numpy as jnp
    import optax

    from dalle_pytorch_tpu.parallel.mesh import make_runtime
    from dalle_pytorch_tpu.parallel.sharding import (
        opt_state_shardings,
        params_shardings,
    )
    from dalle_pytorch_tpu.parallel.step import (
        TrainState,
        make_train_step,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    SDS = jax.ShapeDtypeStruct
    # ONE device, always: the audit must derive the same signatures and
    # byte budgets on a laptop, under the test suite's 8-fake-device
    # XLA_FLAGS, and on a real pod — the contract is about the program,
    # not the host it was traced on
    runtime = make_runtime(devices=jax.devices()[:1])
    optimizer = optax.adam(1e-3)

    def loss_fn(params, batch, rng):
        text, image = batch
        return dalle.apply({"params": params}, text, image, return_loss=True)

    text = SDS((batch, dalle.text_seq_len), jnp.int32)
    image = SDS((batch, dalle.image_seq_len), jnp.int32)
    params = jax.eval_shape(
        lambda t, i: dalle.init(jax.random.key(0), t, i), text, image
    )["params"]
    opt_state = jax.eval_shape(optimizer.init, params)
    i32 = SDS((), jnp.int32)
    state = TrainState(
        step=i32, params=params, opt_state=opt_state,
        skipped=i32, consec_skipped=i32,
    )
    p_shard = params_shardings(params, runtime.mesh)
    replicated = NamedSharding(runtime.mesh, P())
    shardings = TrainState(
        step=replicated, params=p_shard,
        opt_state=opt_state_shardings(opt_state, p_shard, runtime.mesh),
        skipped=replicated, consec_skipped=replicated,
    )
    train_step = make_train_step(
        loss_fn, optimizer, runtime, shardings, donate=True
    )
    key = jax.eval_shape(lambda: jax.random.key(0))
    return EntryPoint(
        name="train.step",
        path="dalle_pytorch_tpu/parallel/step.py",
        symbol="make_train_step",
        fn=train_step,
        lower=train_step.lower,
        static_argnums=(),
        donate={"state": 0},
        signatures=[Signature("step", (state, (text, image), key))],
    )
