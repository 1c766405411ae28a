#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the flagship model (BASELINE.json row 2: DALLE dim 1024, depth 12,
16 heads x 64, 256 text + 1024 image tokens, bf16, over a 256-px
DiscreteVAE with 8192 codes) once through the entry points a user calls,
on whatever accelerator jax finds — and refuses to run without one:

    python chip_smoke.py

Phases, each a child process (a chip belongs to one process at a time, so
this parent never imports jax or any package module that does):

  probe        what jax sees; anything but a TPU backend stops here
  train_vae    train_vae.py, a few steps at 256 px
  train_dalle  train_dalle.py --vae_path ... --bf16: every step's loss
               finite, the last below the first, checkpoint + manifest
  generate     generate.py --bf16: requests through serving.Engine in its
               default config with the VAE-decode stage, all COMPLETED
  checks       what the CLIs cannot show: the same Request replayed on one
               flagship Engine is bit-identical; the fused ragged
               iteration compiles and serves; every Pallas kernel
               compiles with interpret=False at the flagship's shapes and
               matches its jnp reference

The three CLI phases run the scripts themselves (``runpy`` as
``__main__``, same argv a shell would pass) inside a thin child that first
asserts the backend is a TPU and afterwards reports what the process
compiled: compile requests and persistent-cache hits, which attention
implementation each jit took (ops/kv_policy.py:ROUTE_LOG) and which lowered
programs contain Mosaic ``tpu_custom_call``s.

Datasets, checkpoints and IR dumps go to a temporary directory outside the
checkout and are removed (no file there is large: a plain checkpoint above
utils/checkpoint.py:PART_BYTES is written in parts, because a machine may cap
file sizes — the header prints the limit in force); the compile cache is the
only thing left behind
(dalle_pytorch_tpu/compile_cache.py: ``JAX_COMPILATION_CACHE_DIR`` if set,
else ``<checkout>/.jax_cache``).

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Any failing phase makes the exit code non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import math
import resource
import shutil
import signal
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent

# what the parent needs beside itself; a directory holding only this file
# fails here, before any phase
NEEDED = ("train_vae.py", "train_dalle.py", "generate.py", "dalle_pytorch_tpu")

# the flagship (BASELINE.json row 2)
DIM, DEPTH, HEADS, DIM_HEAD = 1024, 12, 16, 64
TEXT_SEQ, IMAGE_SIZE, VAE_LAYERS, NUM_TOKENS = 256, 256, 3, 8192
SEQ = TEXT_SEQ + (IMAGE_SIZE // 2**VAE_LAYERS) ** 2  # 1280

# global batch: the 1, 2 or 4 chips of a v5e host all divide it, so
# make_runtime()'s dp = every visible device never trips check_batch_size
# (8 does not fit: with the tokenizer's 49,408-word vocabulary the f32
# logits alone are 2.4 GB of a 16 GB chip beside params + Adam moments)
BATCH = 4
TRAIN_STEPS = 6
N_IMAGES = BATCH * TRAIN_STEPS
SERVE_REQUESTS = 4

# serving checks: page geometry of the flagship slot (257 + 1024 positions
# in 128-row pages), the fused block widths in use (1 + spec_k, and a
# prefill chunk of T // 16)
PAGE, SLOT_PAGES = 128, 11
SPEC_WIDTH, CHUNK_WIDTH = 4, 16
REPLAY_NEW_TOKENS, FUSED_NEW_TOKENS = 256, 64
# a model trained for six steps on a few dozen images is all but
# deterministic at temperature 1; the replay check samples hot so that
# "bit-identical" is a statement about the (seed, position) sampling
# streams and the device's numerics, not about a collapsed distribution
# (temperature is a traced operand: same compiled programs as generate.py)
REPLAY_TEMPERATURE = 100.0

CAPTION_COLORS = {
    "red": (220, 40, 40), "green": (40, 200, 60), "blue": (50, 70, 230),
    "yellow": (230, 220, 50), "purple": (160, 60, 200),
    "orange": (240, 140, 40),
}
CAPTION_SHAPES = ("square", "circle", "stripe")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ===================================================================== parent


def build_dataset(root: Path, n: int, size: int) -> None:
    """``n`` seeded image/caption pairs of coloured shapes on noise (the
    examples/rainbow.py idea at 256 px; the noise keeps the VAE's codebook
    usage from collapsing to one code in a handful of steps)."""
    import numpy as np
    from PIL import Image

    rng = np.random.RandomState(0)
    root.mkdir(parents=True)
    yy, xx = np.mgrid[:size, :size]
    half, r = size // 2, int(size * 0.28)
    colors = list(CAPTION_COLORS)
    for i in range(n):
        color = colors[i % len(colors)]
        shape = CAPTION_SHAPES[(i // len(colors)) % len(CAPTION_SHAPES)]
        arr = rng.randint(0, 64, size=(size, size, 3)).astype(np.uint8)
        if shape == "square":
            m = (abs(yy - half) < r) & (abs(xx - half) < r)
        elif shape == "circle":
            m = (yy - half) ** 2 + (xx - half) ** 2 < r * r
        else:
            m = (yy // (size // 8)) % 2 == 0
        arr[m] = np.asarray(CAPTION_COLORS[color], np.uint8)
        stem = root / f"sample_{i:04d}"
        Image.fromarray(arr).save(stem.with_suffix(".png"))
        stem.with_suffix(".txt").write_text(f"a {color} {shape}")


def run_child(mode: str, *args: str, report: Path) -> dict:
    """One phase in its own process; a non-zero exit raises (no phase is
    allowed to fail quietly). Returns the report the child wrote."""
    subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--child", mode,
         "--report", str(report), *args],
        check=True, cwd=REPO,
    )
    return json.loads(report.read_text())


def probe() -> dict:
    """Ask a child what jax sees. The child's stdout is the report (the
    parent has no scratch directory yet: nothing is written before the
    device is known to be there)."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--child", "probe"],
        cwd=REPO, stdout=subprocess.PIPE, text=True,
    )
    if proc.returncode != 0:
        raise SystemExit(
            f"chip_smoke: the probe child failed (exit {proc.returncode}); "
            "jax could not initialise a backend"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def describe(phase: str, rep: dict) -> None:
    log(
        f"{phase}: backend {rep['backend']}, {rep['compile_requests']} compile "
        f"requests = {rep['backend_compiles']} backend compiles + "
        f"{rep['cache_hits']} persistent-cache hits "
        f"({rep['compile_secs']:.1f} s compiling or loading)"
    )
    for r in rep["routes"]:
        mode = {None: "jnp", True: "INTERPRETED", False: "Mosaic"}[r["interpret"]]
        log(f"{phase}:   route {r['site']} -> {r['impl']} [{mode}]")
    for name, n in sorted(rep["mosaic_modules"].items()):
        log(f"{phase}:   {name}: {n} tpu_custom_call(s)")


def check_routes(phase: str, rep: dict, expected: list[tuple]) -> None:
    """Every expected (site, impl, interpret) must be in the child's route
    log, and NOTHING in it may have run a Pallas kernel interpreted."""
    got = {(r["site"], r["impl"], r["interpret"]) for r in rep["routes"]}
    interpreted = [r for r in rep["routes"] if r["interpret"]]
    if interpreted:
        raise SystemExit(f"chip_smoke: {phase} interpreted Pallas kernels: {interpreted}")
    missing = [e for e in expected if e not in got]
    if missing:
        raise SystemExit(
            f"chip_smoke: {phase} did not take the expected attention "
            f"route(s) {missing}; it took {sorted(got, key=str)}"
        )


def require_mosaic(phase: str, rep: dict, module: str) -> None:
    """The lowered program of jit ``module`` must hold Mosaic custom calls:
    proof, not assumption, that its kernels took the compiled branch."""
    if not rep["mosaic_modules"].get(module):
        raise SystemExit(
            f"chip_smoke: {phase}: {module} holds no Mosaic tpu_custom_call "
            f"(modules with one: {rep['mosaic_modules']})"
        )


def read_losses(flight_dir: Path) -> list[float]:
    """Per-step losses from train_dalle.py --telemetry's flight recorder:
    every ``train.step`` span closes with the step's loss
    (parallel/loop.py)."""
    flights = sorted(flight_dir.glob("flight-*.jsonl"))
    if not flights:
        raise SystemExit(f"chip_smoke: no flight-recorder file under {flight_dir}")
    losses = []
    for line in flights[0].read_text().splitlines():
        rec = json.loads(line)
        if rec.get("name") == "train.step" and rec.get("ph") == "E":
            losses.append(float(rec["loss"]))
    return losses


def main() -> int:
    missing = [n for n in NEEDED if not (REPO / n).exists()]
    if missing:
        print(
            f"chip_smoke: {REPO} holds no {', '.join(missing)} — this script "
            "checks the repository it sits in and is nothing without it",
            file=sys.stderr,
        )
        return 2

    # a killed run (the tool's time limit) still stops its child and
    # removes its scratch: SystemExit unwinds subprocess.run, which kills
    # the child, and the finally below
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    t_start = time.monotonic()
    dev = probe()
    if dev["backend"] != "tpu":
        print(
            f"chip_smoke: no accelerator — jax's default backend is "
            f"{dev['backend']!r} ({dev['count']} x {dev['kind']}); this check "
            "runs on a TPU only and starts no phase without one",
            file=sys.stderr,
        )
        return 3
    if BATCH % dev["count"]:
        print(
            f"chip_smoke: {dev['count']} chips do not divide the global "
            f"batch {BATCH}", file=sys.stderr,
        )
        return 3

    from dalle_pytorch_tpu.compile_cache import cache_dir

    cache = Path(cache_dir())
    log(
        f"device: {dev['count']} x {dev['kind']} (platform {dev['platform']}); "
        f"training uses all {dev['count']} as dp, serving uses 1"
    )
    log(f"versions: jax {dev['jax']}, jaxlib {dev['jaxlib']}, libtpu {dev['libtpu']}")
    log(f"compile cache: {cache} ({_n_entries(cache)} entries at start)")
    fsize = resource.getrlimit(resource.RLIMIT_FSIZE)[0]
    log(
        "file-size limit (RLIMIT_FSIZE): "
        + ("none" if fsize == resource.RLIM_INFINITY else f"{fsize / 2**20:.0f} MiB")
    )

    phases: list[tuple[str, float]] = []

    def phase(name: str, fn):
        t0 = time.monotonic()
        out = fn()
        phases.append((name, time.monotonic() - t0))
        log(f"phase {name}: {phases[-1][1]:.1f} s")
        return out

    # outside the checkout: the flagship checkpoint with Adam moments is
    # several GB and the chip tool refuses to copy a tree that large back
    work = Path(tempfile.mkdtemp(prefix="dalle_chip_smoke_"))
    try:
        data = work / "data"
        phase("dataset", lambda: build_dataset(data, N_IMAGES, IMAGE_SIZE))

        vae_ckpt = work / "vae.ckpt"
        rep = phase("train_vae", lambda: run_child(
            "cli", "train_vae.py",
            "--image_folder", str(data),
            "--image_size", str(IMAGE_SIZE),
            "--num_layers", str(VAE_LAYERS),
            "--num_tokens", str(NUM_TOKENS),
            "--batch_size", str(BATCH),
            "--epochs", "1",
            "--output_file_name", str(vae_ckpt),
            "--samples_dir", str(work / "vae_samples"),
            report=work / "train_vae.json",
        ))
        describe("train_vae", rep)
        assert vae_ckpt.exists(), "train_vae.py wrote no checkpoint"

        dalle_out = work / "dalle"
        flight = work / "flight"
        rep = phase("train_dalle", lambda: run_child(
            "cli", "train_dalle.py",
            "--image_text_folder", str(data),
            "--vae_path", str(vae_ckpt),
            "--bf16",
            "--dim", str(DIM), "--depth", str(DEPTH),
            "--heads", str(HEADS), "--dim_head", str(DIM_HEAD),
            "--text_seq_len", str(TEXT_SEQ),
            "--attn_types", "full",
            "--shift_tokens", "--rotary_emb",
            "--batch_size", str(BATCH),
            "--epochs", "1",
            "--truncate_captions",
            "--dalle_output_file_name", str(dalle_out),
            "--telemetry", "--telemetry_dir", str(flight),
            report=work / "train_dalle.json",
        ))
        describe("train_dalle", rep)
        check_routes("train_dalle", rep, [("forward/full", "fused_qkv_flash", False)])
        require_mosaic("train_dalle", rep, "jit_train_step")
        losses = read_losses(flight)
        log(f"train_dalle: losses {[round(x, 4) for x in losses]}")
        if len(losses) < 3 or not all(math.isfinite(x) for x in losses):
            raise SystemExit(f"chip_smoke: need >= 3 finite training losses, got {losses}")
        if not losses[-1] < losses[0]:
            raise SystemExit(f"chip_smoke: loss did not fall: {losses}")
        dalle_ckpt = Path(f"{dalle_out}.ckpt")
        manifest = Path(f"{dalle_ckpt}.manifest.json")
        assert dalle_ckpt.exists() and manifest.exists(), (
            "train_dalle.py left no checkpoint + manifest sidecar"
        )
        sizes = [f.stat().st_size for f in work.glob(f"{dalle_ckpt.name}*")]
        log(
            f"train_dalle: checkpoint {sum(sizes) / 2**30:.2f} GiB in "
            f"{len(sizes)} files, the largest {max(sizes) / 2**20:.0f} MiB"
        )

        outputs = work / "outputs"
        rep = phase("generate", lambda: run_child(
            "cli", "generate.py",
            "--dalle_path", str(dalle_ckpt),
            "--text", "a red square",
            "--num_images", str(SERVE_REQUESTS),
            "--batch_size", str(SERVE_REQUESTS),
            "--bf16",
            "--outputs_dir", str(outputs),
            report=work / "generate.json",
        ))
        describe("generate", rep)
        # default EngineConfig: monolithic prefill + split decode over the
        # paged pools — no Pallas kernel, the gathered view + einsum chain
        check_routes("generate", rep, [
            ("decode_block", "cache_block_attend", None),
            ("decode_token", "lane_packed_einsum", None),
        ])
        check_images(outputs, SERVE_REQUESTS)

        rep = phase("checks", lambda: run_child(
            "checks", str(dalle_ckpt), report=work / "checks.json",
        ))
        describe("checks", rep)
        check_routes("checks", rep, [
            ("ragged_block/full", "ragged_paged_kernel", False),
        ])
        require_mosaic("checks", rep, "jit__iteration_jit")
        for line in rep["lines"]:
            log(f"checks:   {line}")
    finally:
        shutil.rmtree(work, ignore_errors=True)

    log(f"compile cache: {cache} ({_n_entries(cache)} entries at end)")
    log(
        "wall: " + ", ".join(f"{n} {s:.1f} s" for n, s in phases)
        + f"; total {time.monotonic() - t_start:.1f} s"
    )
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
    }}), flush=True)
    return 0


def _n_entries(cache: Path) -> int:
    return sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0


def check_images(outputs: Path, want: int) -> None:
    """generate.py raises unless every request COMPLETED with in-vocabulary
    tokens and finite pixels (generate.py:_engine_images); here, that it
    also wrote every image at the VAE's resolution."""
    import numpy as np
    from PIL import Image

    pngs = sorted(outputs.glob("*/*.png"))
    if len(pngs) != want:
        raise SystemExit(f"chip_smoke: generate.py wrote {len(pngs)} images, want {want}")
    for p in pngs:
        arr = np.asarray(Image.open(p))
        if arr.shape != (IMAGE_SIZE, IMAGE_SIZE, 3):
            raise SystemExit(f"chip_smoke: {p} has shape {arr.shape}")
    log(f"generate: {len(pngs)} images of {IMAGE_SIZE}x{IMAGE_SIZE}")


# =================================================================== children


def child_probe() -> int:
    import importlib.metadata as md

    import jax
    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    d = jax.devices()[0]
    print(json.dumps({
        "backend": jax.default_backend(),
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }), flush=True)
    return 0


class ChildReport:
    """What a child that owns the chip observes about itself: installed
    before its work, written after. Refuses to start off-TPU — the one
    assertion that, with kv_policy.on_tpu() being the only platform
    decision in ops/, rules out a silently interpreted kernel."""

    def __init__(self, path: str):
        import jax

        from dalle_pytorch_tpu.compile_cache import enable_compile_cache

        if jax.default_backend() != "tpu":
            raise SystemExit(
                f"chip_smoke child: backend is {jax.default_backend()!r}, not tpu"
            )
        # installs the compile ledger, which counts this child's requests
        enable_compile_cache()
        self.path = Path(path)
        self.ir_dir = self.path.with_suffix(".ir")
        jax.config.update("jax_dump_ir_to", str(self.ir_dir))
        # persist every program, not only those that took over a second to
        # compile (jax's default): each child is a short-lived process
        # that requests hundreds of small ones, and a warm run should
        # show (almost) no backend compile at all
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.lines: list[str] = []

    def mosaic_modules(self) -> dict:
        """{jit module name: number of Mosaic custom calls in its lowered
        program}, from the StableHLO jax dumped at compile time (dumped
        before the cache lookup, so warm runs report the same)."""
        found: dict = {}
        for f in sorted(self.ir_dir.glob("*.mlir")):
            n = f.read_text().count("tpu_custom_call")
            if n:
                # jax_ir0012_jit_train_step_compile.mlir -> jit_train_step
                name = f.name.split("_", 2)[2].rsplit("_compile", 1)[0]
                found[name] = max(n, found.get(name, 0))
        return found

    def write(self) -> None:
        import jax

        from dalle_pytorch_tpu.ops import kv_policy
        from dalle_pytorch_tpu.utils.profiling import COMPILE_LEDGER

        compiles = COMPILE_LEDGER.summary()
        self.path.write_text(json.dumps({
            "backend": jax.default_backend(),
            "compile_requests": compiles["requests"],
            "cache_hits": compiles["cache_hits"],
            "backend_compiles": compiles["cache_misses"],
            "compile_secs": compiles["seconds"]["backend"],
            "routes": kv_policy.ROUTE_LOG,
            "mosaic_modules": self.mosaic_modules(),
            "lines": self.lines,
        }))


def child_cli(report: str, script: str, argv: list[str]) -> int:
    """Run one CLI exactly as ``python <script> <argv>`` would, in this
    process, between the report's prologue and epilogue."""
    import runpy

    rep = ChildReport(report)
    sys.argv = [script, *argv]
    runpy.run_path(str(REPO / script), run_name="__main__")
    rep.write()
    return 0


def child_checks(report: str, dalle_ckpt: str) -> int:
    rep = ChildReport(report)
    check_engine(dalle_ckpt, rep.lines)
    check_kernels(rep.lines)
    rep.write()
    return 0


def check_engine(dalle_ckpt: str, lines: list[str]) -> None:
    """(a) replay determinism: one Request, submitted again with the same
    seed to the same flagship Engine — alone, then beside another request
    — returns bit-identical tokens (serving/types.py:Request). (b) the
    fused ragged iteration serves the flagship through the Pallas kernel."""
    import numpy as np

    from dalle_pytorch_tpu.models.factory import dalle_from_checkpoint
    from dalle_pytorch_tpu.serving import Engine, EngineConfig, Outcome, Request
    from dalle_pytorch_tpu.utils.quantize import prepare_for_serving

    t0 = time.monotonic()
    dalle, params, _, _, _ = dalle_from_checkpoint(dalle_ckpt)
    dalle, params = prepare_for_serving(dalle, params)
    lines.append(f"checkpoint restored and cast to bf16 ({time.monotonic() - t0:.1f} s)")
    rng = np.random.RandomState(0)
    prompt = np.zeros(dalle.text_seq_len, np.int32)
    prompt[:8] = rng.randint(1, dalle.num_text_tokens, size=8)

    def serve(engine, ids_seeds, max_new):
        for rid, seed in ids_seeds:
            rejected = engine.submit(Request(
                request_id=rid, prompt=prompt, max_new_tokens=max_new, seed=seed,
            ))
            assert rejected is None, rejected
        results = engine.run()
        out = []
        for rid, _ in ids_seeds:
            r = results[rid]
            assert r.outcome is Outcome.COMPLETED, (rid, r.outcome, r.detail)
            assert len(r.tokens) == max_new, (rid, len(r.tokens))
            assert r.tokens.min() >= 0 and r.tokens.max() < dalle.num_image_tokens
            out.append(np.asarray(r.tokens))
        return out

    n = REPLAY_NEW_TOKENS
    t0 = time.monotonic()
    engine = Engine(dalle, params, EngineConfig(
        max_batch=SERVE_REQUESTS, temperature=REPLAY_TEMPERATURE,
    ))
    (first,) = serve(engine, [("a", 7)], n)
    again, other = serve(engine, [("b", 7), ("c", 8)], n)
    distinct = len(np.unique(first))
    assert distinct > n // 8, (
        f"only {distinct} distinct tokens in {n}: the sampler has no entropy "
        "and replaying it proves nothing"
    )
    assert np.array_equal(first, again), (
        "replay with the same seed is not bit-identical: "
        f"{int((first != again).sum())} of {n} tokens differ"
    )
    assert not np.array_equal(first, other), "another seed sampled the same tokens"
    lines.append(
        f"replay determinism: {n} tokens ({distinct} distinct) bit-identical "
        f"alone and beside another request ({time.monotonic() - t0:.1f} s)"
    )

    t0 = time.monotonic()
    fused = Engine(dalle, params, EngineConfig(
        max_batch=SERVE_REQUESTS, temperature=REPLAY_TEMPERATURE,
        prefill_chunk=CHUNK_WIDTH, fused_iteration=True,
    ))
    f_a, f_b = serve(fused, [("fa", 7), ("fb", 8)], FUSED_NEW_TOKENS)
    agree = float((f_a == first[:FUSED_NEW_TOKENS]).mean())
    lines.append(
        f"fused iteration: 2 requests x {FUSED_NEW_TOKENS} tokens COMPLETED, "
        f"{fused.dispatches} dispatches / {fused.iterations} iterations; "
        f"token agreement with the split engine {agree:.2f} (bf16: not a "
        f"bitwise contract) ({time.monotonic() - t0:.1f} s)"
    )


def check_kernels(lines: list[str], interpret: bool = False, n: int = SEQ,
                  heads: int = HEADS, d: int = DIM_HEAD, text: int = TEXT_SEQ,
                  rows: int = 8, n_pages: int = SLOT_PAGES, page: int = PAGE,
                  widths: tuple = (SPEC_WIDTH, CHUNK_WIDTH)) -> None:
    """Every Pallas kernel the flagship (or its sparse variants) can
    route to, compiled by Mosaic (``interpret=False``) at the flagship's
    shapes in bf16 and compared with its jnp reference in the repo. Every
    kernel is tried; the phase fails after the last one if any did not
    compile or did not match, carrying the compiler's own words."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dalle_pytorch_tpu.ops import block_sparse_attention as bs
    from dalle_pytorch_tpu.ops import masks, paged_kv
    from dalle_pytorch_tpu.ops import ragged_attention as ra
    from dalle_pytorch_tpu.ops.attention import dense_attend
    from dalle_pytorch_tpu.ops.flash_attention import (
        StaticMask, StaticTable, flash_attention, fused_qkv_attention,
    )
    from dalle_pytorch_tpu.ops.rotary import apply_rotary_emb, dalle_rotary_table

    dt = jnp.bfloat16
    b = 2
    fmap = int(round((n - text) ** 0.5))
    scale = d**-0.5
    key = jax.random.key(0)
    failures = []

    def close(got, want, tol=4e-2):
        """Largest error over all outputs, relative to the reference's own
        largest magnitude (bf16 inputs: a few 1e-3 when right, O(1) when
        a block, a mask or a scale is wrong)."""
        got = [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(got)]
        want = [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(want)]
        assert all(np.isfinite(g).all() for g in got), "non-finite output"
        err = max(
            float(np.abs(g - w).max() / np.abs(w).max()) for g, w in zip(got, want)
        )
        assert err <= tol, f"relative error {err:.3g} > {tol}"
        return err

    def attempt(name, fn):
        t0 = time.monotonic()
        try:
            err = fn()
        except Exception as e:  # reported in full below; the phase still fails
            failures.append(name)
            lines.append(f"kernel {name}: FAILED — {type(e).__name__}: {str(e)[:2000]}")
            return
        lines.append(
            f"kernel {name}: compiled, relative error {err:.2g} "
            f"({time.monotonic() - t0:.1f} s)"
        )

    causal = masks.causal_mask(n)
    # the patterns are defined over the internal length (text + <bos>); the
    # training forward sees their leading n x n corner
    axial = masks.axial_mask(text + 1, fmap, axis=0)[:n, :n]
    conv = masks.conv_mask(text + 1, fmap, 5, 1)[:n, :n]

    def split(qkv):
        return (
            t.reshape(b, n, heads, d).transpose(0, 2, 1, 3)
            for t in jnp.split(qkv, 3, axis=-1)
        )

    def dense(q, k, v, mask_np):
        return dense_attend(q * scale, k, v, jnp.asarray(mask_np)[None, None])

    def grads(f, *xs):
        cot = jax.random.normal(jax.random.key(9), f(*xs).shape, dt)
        return jax.grad(
            lambda *a: (f(*a).astype(jnp.float32) * cot).sum(),
            argnums=tuple(range(len(xs))),
        )(*xs)

    # ---- fused-qkv flash (the training route): forward + backward, with
    # the in-kernel rotary the flagship trains with and a pattern operand
    qkv = (jax.random.normal(key, (b, n, 3 * heads * d)) * 0.5).astype(dt)
    table = dalle_rotary_table(d, text + 1, fmap)[:n]
    table = np.pad(table, ((0, 0), (0, d - table.shape[1]))).astype(np.float32)
    rot = StaticTable(table)
    for label, mask_np, pattern, rot_ in (
        ("causal+rotary", causal, None, rot),
        ("axial_row", axial, StaticMask(axial), None),
    ):
        def fused(x, pattern=pattern, rot_=rot_):
            return fused_qkv_attention(
                x, None, heads, d, rot_, True, pattern, scale, interpret
            )

        def ref(x, mask_np=mask_np, rot_=rot_):
            q, k, v = split(x)
            if rot_ is not None:
                ang = jnp.asarray(rot_.table)[None, None]
                q, k, v = (apply_rotary_emb(ang, t) for t in (q, k, v))
            return dense(q, k, v, mask_np).transpose(0, 2, 1, 3).reshape(b, n, -1)

        attempt(f"fused_qkv_flash/{label}/fwd", lambda: close(
            jax.jit(fused)(qkv), jax.jit(ref)(qkv)))
        attempt(f"fused_qkv_flash/{label}/bwd", lambda: close(
            jax.jit(lambda x: grads(fused, x))(qkv),
            jax.jit(lambda x: grads(ref, x))(qkv)))

    # ---- blocked flash: whole-row block (fused bwd) and a tiled grid -----
    q, k, v = ((jax.random.normal(kk, (b, heads, n, d)) * 0.5).astype(dt)
               for kk in jax.random.split(key, 3))
    for block in sorted({n, n // 2}):
        def flash(q, k, v, block=block):
            return flash_attention(
                q, k, v, None, True, None, scale, block, block, interpret
            )

        attempt(f"flash/block{block}/fwd", lambda: close(
            jax.jit(flash)(q, k, v),
            jax.jit(lambda q, k, v: dense(q, k, v, causal))(q, k, v)))
        attempt(f"flash/block{block}/bwd", lambda: close(
            jax.jit(lambda *a: grads(flash, *a))(q, k, v),
            jax.jit(lambda *a: grads(
                lambda q, k, v: dense(q, k, v, causal), *a))(q, k, v)))

    # ---- block-sparse pair grid: axial_row and conv_like -----------------
    for label, mask_np in (("axial_row", axial), ("conv_like", conv)):
        layout = bs.compile_block_layout(mask_np, 128, 128)

        def sparse(q, k, v, layout=layout):
            return bs.block_sparse_attention(
                q, k, v, layout, sm_scale=scale, interpret=interpret
            )

        def sref(q, k, v, layout=layout):
            return bs.reference_attend(q, k, v, layout, sm_scale=scale)

        attempt(f"block_sparse/{label}/fwd", lambda: close(
            jax.jit(sparse)(q, k, v), jax.jit(sref)(q, k, v)))
        attempt(f"block_sparse/{label}/bwd", lambda: close(
            jax.jit(lambda *a: grads(sparse, *a))(q, k, v),
            jax.jit(lambda *a: grads(sref, *a))(q, k, v)))

    # ---- ragged paged attention: bf16 pools and int8 pools + scales ------
    hd = heads * d
    rng = np.random.RandomState(1)
    pools = [
        jnp.asarray(rng.randn(rows, n_pages, page, hd) * 0.5, dt) for _ in range(2)
    ]
    table = paged_kv.identity_table(rows, n_pages)
    cap = n_pages * page
    for width in widths:
        # a mixed iteration: decode rows at scattered frontiers, a prefill
        # chunk, an idle row, a row ending on the last position
        start = jnp.asarray(
            [(37 * r * page // 7) % (cap - width) for r in range(rows - 1)]
            + [cap - width], jnp.int32,
        )
        length = jnp.asarray(
            [1, width, 0] + [1 + r % width for r in range(rows - 3)], jnp.int32
        )
        qr = jnp.asarray(rng.randn(rows, width, heads, d) * 0.5, dt) * scale
        pos = start[:, None] + jnp.arange(width)[None]
        allowed = (jnp.arange(cap)[None, None] <= pos[..., None])[:, None]
        valid = (jnp.arange(width)[None] < length[:, None])[..., None, None]
        for quant in (False, True):
            if quant:
                flat = [p.reshape(rows, cap, hd) for p in pools]
                (kq, ks), (vq, vs) = (paged_kv.quantize_rows(f, heads) for f in flat)
                kv = [x.reshape(rows, n_pages, page, hd) for x in (kq, vq)]
                scales = dict(
                    k_scales=ks.reshape(rows, n_pages, page, heads),
                    v_scales=vs.reshape(rows, n_pages, page, heads),
                )
            else:
                kv, scales = pools, {}

            # the pools are operands, not closure constants: a jit that
            # closes over 46 MB of them carries 92 MB of hex text in its IR
            # dump and as much in its cache entry, and a machine that caps
            # file sizes refuses both
            def kernel(qr, k, v, scales, start=start, length=length):
                return ra.kernel_attend(
                    qr, k, v, table, start, length,
                    interpret=interpret, **scales,
                )

            def rref(qr, k, v, scales, allowed=allowed):
                return ra.reference_attend(qr, k, v, table, allowed, **scales)

            attempt(
                f"ragged/{'int8' if quant else 'bf16'}/width{width}",
                lambda ops=(qr, kv[0], kv[1], scales): close(
                    jnp.where(valid, jax.jit(kernel)(*ops), 0),
                    jnp.where(valid, jax.jit(rref)(*ops), 0)),
            )

    if failures:
        raise SystemExit(
            "chip_smoke: kernels failed: " + ", ".join(failures) + "\n"
            + "\n".join(l for l in lines if "FAILED" in l)
        )


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--child", choices=("probe", "cli", "checks"),
                    help="internal: run one phase in this process")
    ap.add_argument("--report", help="internal: where a child writes its report")
    ap.add_argument("rest", nargs=argparse.REMAINDER)
    a = ap.parse_args()
    if a.child is None:
        sys.exit(main())
    if a.child == "probe":
        sys.exit(child_probe())
    if a.child == "cli":
        sys.exit(child_cli(a.report, a.rest[0], a.rest[1:]))
    sys.exit(child_checks(a.report, a.rest[0]))
