"""Serving cells: the window drives ``Engine.submit`` and ``Engine.step``.

Set-up builds the model in its serving precision with weights from the seed,
one ``Engine``, and brings it to the state a long-running service is in (a
closed backlog: slots at spread-out frontiers). The window then measures;
afterwards a sample of the requests it finished is compared with the plain
reference.

No cell of ``BENCHMARK.json`` uses this driver yet: held to the plain
reference the program's serving path is at fault on the chip (PERF.md, Open
questions), and a cell at fault stays out. The driver stays so that the PR
that repairs the program can add the serving cell by data files alone; the
tests drive it on the CPU (``tests/test_correct.py``).
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from .. import costs, harness, reference, traffic, weights

def build_dalle(cfg: dict):
    """The program's DALLE module for a configuration file, computing in the
    precision the file states."""
    import jax.numpy as jnp
    from dalle_pytorch_tpu.models import DALLE

    return DALLE(
        dim=cfg["dim"], depth=cfg["depth"], heads=cfg["heads"], dim_head=cfg["dim_head"],
        num_text_tokens=cfg["num_text_tokens"], text_seq_len=cfg["text_seq_len"],
        num_image_tokens=cfg["num_image_tokens"], image_fmap_size=cfg["image_fmap_size"],
        attn_types=tuple(cfg["attn_types"]), shift_tokens=cfg["shift_tokens"],
        rotary_emb=cfg["rotary_emb"], reversible=cfg["reversible"],
        loss_img_weight=cfg["loss_img_weight"], dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def param_shapes(dalle, cfg: dict):
    import jax
    import jax.numpy as jnp

    text = jnp.zeros((1, cfg["text_seq_len"]), jnp.int32)
    image = jnp.zeros((1, costs.image_len(cfg)), jnp.int32)
    return jax.eval_shape(dalle.init, jax.random.key(0), text, image)["params"]


class Loop:
    """The engine with the harness's own book-keeping around it: when each
    request was sent and finished, and after every step how many slots
    decode and how far their frontiers reach."""

    def __init__(self, engine, stream):
        self.engine, self.stream = engine, stream
        self.sent = {}        # request id -> (Planned, submit time)
        self.finished = {}    # request id -> finish time (harness clock)
        self.refused = 0
        self._seen = 0
        self.samples = []     # (live decode slots, sum of their frontiers)
        self.sampling = False
        self.alive = True     # False once a step found the engine fully idle

    def submit(self, planned, now: float) -> None:
        from dalle_pytorch_tpu.serving import Request

        rid = f"r{planned.index}"
        with harness.span("bench.submit"):
            result = self.engine.submit(Request(
                request_id=rid, prompt=planned.prompt,
                max_new_tokens=planned.max_new_tokens, seed=planned.seed,
            ))
        self.sent[rid] = (planned, now)
        if result is not None:
            self.refused += 1

    def step(self) -> list:
        """One engine iteration. Returns the ids that finished in it."""
        with harness.span("bench.step"):
            self.alive = self.engine.step()
        done = []
        results = self.engine.results
        if len(results) != self._seen:
            now = time.monotonic()
            for rid in list(results)[self._seen:]:
                self.finished[rid] = now
                done.append(rid)
            self._seen = len(results)
        if self.sampling:
            live = [s for s in self.engine.slots if s and s.phase == "decode"]
            self.samples.append((len(live), sum(s.pos for s in live)))
        return done

    def committed_tokens(self) -> int:
        """Image tokens committed so far: those of finished requests and
        those read back for requests still in their slots."""
        n = sum(len(r.tokens) for r in self.engine.results.values() if r.tokens is not None)
        return n + sum(len(s.entry.generated) for s in self.engine.slots if s)


def run(ctx) -> None:
    import jax
    import jax.numpy as jnp
    from dalle_pytorch_tpu.serving import Engine, EngineConfig

    mix, cfg = ctx.mix, ctx.cfg
    dalle = build_dalle(cfg)
    params = weights.make_params(
        param_shapes(dalle, cfg), ctx.seed, jnp.dtype(cfg["serve_param_dtype"])
    )
    engine_kwargs = dict(mix["engine"], temperature=float(mix["temperature"]))
    if ctx.control == "int8":
        # the program's own lower-precision path, switched on: int8 weights
        # and int8 pages (utils/quantize.py, EngineConfig.kv_quant)
        from dalle_pytorch_tpu.utils.quantize import quantize_dalle

        serve_dalle, serve_params = quantize_dalle(dalle, params)
        engine_kwargs["kv_quant"] = "int8"
    else:
        serve_dalle, serve_params = dalle, params
    engine = Engine(serve_dalle, serve_params, EngineConfig(**engine_kwargs), stages=None)
    stream = traffic.RequestStream(mix, cfg, ctx.seed)
    loop = Loop(engine, stream)
    compiles = ctx.facts["compile_counter"]

    if mix["kind"] == "closed_backlog":
        window = _closed_backlog(ctx, loop)
    else:
        raise SystemExit(f"driver 'serve' knows no traffic kind {mix['kind']!r}")
    ctx.compiles_in_window = compiles.n - window["compiles_before"]

    ctx.memory_peak_bytes = harness.memory_peak(jax.local_devices()[: ctx.chips])
    results = dict(engine.results)
    ctx.facts.update(window, samples=loop.samples)
    sample = _check_sample(ctx, loop, results, window)
    # the program's state goes before the reference runs
    engine.cache = None
    del engine, loop.engine, serve_params, serve_dalle
    gc.collect()
    _compare(ctx, params, sample)


# --------------------------------------------------------------- windows


def _closed_backlog(ctx, loop) -> dict:
    mix = ctx.mix
    outstanding = int(mix["outstanding"])
    now = time.monotonic()
    for budget in traffic.stagger_budgets(mix, ctx.seed):
        loop.submit(loop.stream.next(max_new_tokens=budget), now)
    while len(loop.sent) < outstanding:
        loop.submit(loop.stream.next(), now)

    def replace(done):
        now = time.monotonic()
        for _ in done:
            loop.submit(loop.stream.next(), now)

    # set-up the traffic needs. After max_new_tokens iterations the last
    # staggered request is out and every slot holds a full-length request
    # somewhere along its way; a mix may settle for fewer (PERF.md, cells)
    ramp = int(mix.get("ramp_iterations", mix["max_new_tokens"]))
    for _ in range(ramp):
        replace(loop.step())

    ctx.facts["setup_s"] = time.monotonic() - ctx.process_start
    compiles_before = ctx.facts["compile_counter"].n
    t0 = time.monotonic()
    tokens0, iters0 = loop.committed_tokens(), loop.engine.iterations
    sent0, done0, refused0 = len(loop.sent), set(loop.finished), loop.refused
    tracer = harness.TraceSlice(ctx, t0)
    loop.sampling = True
    now = t0
    while now < t0 + ctx.seconds:
        tracer.maybe_start(now)
        replace(loop.step())
        now = time.monotonic()
        tracer.maybe_stop(now)
    tracer.maybe_stop(now, force=True)
    loop.sampling = False
    t1 = time.monotonic()
    tokens1 = loop.committed_tokens()
    elapsed = t1 - t0
    ctx.end_to_end["serve_tokens_per_s"] = (tokens1 - tokens0) / elapsed
    ctx.reduced = tracer.reduce(ctx.chips)
    finished = [r for r in loop.finished if r not in done0]
    results = loop.engine.results
    ctx.attempted = len(loop.sent) - sent0
    ctx.failed = loop.refused - refused0 + sum(
        1 for r in finished if results[r].outcome.value != "completed"
    )
    return {
        "compiles_before": compiles_before, "window_s": elapsed,
        "tokens": tokens1 - tokens0, "iterations": loop.engine.iterations - iters0,
        "finished_in_window": finished, "prefills": len(loop.sent) - sent0,
    }


# ------------------------------------------------------------ correctness


def _check_sample(ctx, loop, results: dict, window: dict) -> list:
    """[(prompt row, served tokens)] of ``check_requests`` completed requests
    that the window finished, drawn from the seed, the longest among them."""
    done = [
        rid for rid in window["finished_in_window"]
        if results[rid].outcome.value == "completed"
    ]
    if not done:
        return []
    rng = traffic.rng_for(ctx.seed, "check")
    longest = max(done, key=lambda r: len(results[r].tokens))
    rest = [r for r in done if r != longest]
    rng.shuffle(rest)
    picked = [longest] + rest[: max(int(ctx.mix["check_requests"]) - 1, 0)]
    out = []
    for rid in picked:
        planned, _ = loop.sent[rid]
        tokens = np.asarray(results[rid].tokens, np.int32)
        if len(tokens) != planned.max_new_tokens or tokens.min() < 0 or (
            tokens.max() >= ctx.cfg["num_image_tokens"]
        ):
            ctx.compare(f"tokens_malformed_{rid}", 1.0, 0.0)
        out.append((planned.prompt, tokens))
    return out


def _compare(ctx, params, sample: list) -> None:
    """How far the served tokens lie below the reference's best, in the
    reference's own logits, over every token of the sampled requests:
    ``logit_gap_max`` the widest such gap, ``logit_gap_mean`` their mean (a
    token that is the reference's first choice counts 0). The traffic decodes
    greedily (temperature next to 0), so a served token is the program's own
    first choice at its position. A number is compared where the cell's
    limits file holds a limit for it, and printed otherwise."""
    import jax
    import jax.numpy as jnp

    if not sample:
        ctx.compare("requests_checked", 0.0, -1.0)   # nothing to compare: not correct
        return
    full = costs.image_len(ctx.cfg)

    def gaps_of(cfg, requests) -> np.ndarray:
        logits_of = jax.jit(lambda p, t, i: reference.image_logits(p, cfg, t, i, "f32"))
        out = []
        for prompt, tokens in requests:
            padded = np.zeros((1, full), np.int32)
            padded[0, : len(tokens)] = tokens
            ref = logits_of(params, jnp.asarray(prompt[None]), jnp.asarray(padded))[0]
            ref = ref[: len(tokens)]
            picked = jnp.take_along_axis(ref, jnp.asarray(tokens)[:, None], axis=-1)[:, 0]
            out.append(np.asarray(jnp.max(ref, axis=-1) - picked))
        return np.concatenate(out) if out else np.zeros((0,), np.float32)

    gaps = gaps_of(ctx.cfg, sample)
    ctx.facts["tokens_checked"] = int(gaps.size)
    readings = {
        "logit_gap_max": float(gaps.max()),
        "logit_gap_mean": float(gaps.mean()),
        "not_first_choice_share": float((gaps > 0).mean()),
    }
    for name, value in readings.items():
        if name in ctx.facts["limits"]:
            ctx.compare(name, value, ctx.facts["limits"][name])
        else:
            ctx.facts[f"{name}_not_compared"] = value
            print(f"read, not compared: {name} {value!r}", file=sys.stderr)
