"""The window / global attention cell's readers on a made-up reduction: the
five shares of the step sum to 100, the window layers' and the global layer's
kernels are read by their scope, the router that runs before the attention
counts with the expert layer, the grouped products that reach the trace
without a scope are counted by their name, once; and ``costs_swa`` against a
brute-force count of the band and the configuration's own arithmetic."""

import json
import pathlib
import types

import numpy as np

from benchmarks import costs, costs_swa, scope_reduce
from benchmarks.readers import scope_kernel_share, scope_share, swa_roofline, train_swa_mfu

ROOT = pathlib.Path(__file__).resolve().parents[2]
MOD = "jit_train_step"
CELL = "train-smallthinker-d4-ep4-s16k"
ROWS = [
    ["jit(train_step)/attn.gqa/transformer/mixer_0/norm", "", 0.01],
    ["jit(train_step)/attn.gqa/transformer/mixer_0/fn/flash", "flash_fwd", 0.02],
    ["jit(train_step)/transpose(jvp(attn.gqa))/mixer_0/fn/flash", "flash_bwd", 0.04],
    ["jit(train_step)/attn.swa/transformer/mixer_1/fn/to_q/dot", "", 0.05],
    ["jit(train_step)/attn.swa/transformer/mixer_1/fn/flash", "flash_fwd", 0.03],
    ["jit(train_step)/transpose(jvp(attn.swa))/mixer_1/fn/flash", "flash_bwd", 0.06],
    ["jit(train_step)/transformer/mixer_1/moe/moe.router/gate/dot", "", 0.01],
    ["jit(train_step)/moe/ff_1/fn/moe.router/top_k", "", 0.01],
    ["jit(train_step)/moe/ff_1/fn/moe.dispatch/sort", "", 0.02],
    ["jit(train_step)/moe/ff_1/fn/moe.experts/convert", "", 0.01],
    ["", "ragged-dot-none", 0.07],                      # the grouped products: no scope
    ["jit(train_step)/head_loss", "", 0.06],
    ["jit(train_step)/embed", "", 0.01],
    ["jit(train_step)/update/update.optimizer", "", 0.04],
    ["", "", 0.02],                                     # nothing names it
]


def ctx():
    return types.SimpleNamespace(
        cfg=costs.load_config("smallthinker-21b-a3b-d4-ep4"), chips=1, trace=True,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        reduced={"modules": {MOD: {"count": 1}}, "window_s": 1.0},
        facts={"steps": 30, "window_s": 30.0, "rows": 1, "tokens": 16384,
               "moe_pairs_per_step": 98304.0, scope_reduce.FACT: {"by_scope": {MOD: ROWS}}},
    )


def read(name, c=None):
    spec = json.loads((ROOT / "benchmarks" / "metrics" / f"{name}.json").read_text())
    reader = {"scope_share": scope_share, "scope_kernel_share": scope_kernel_share,
              "swa_roofline": swa_roofline, "train_swa_mfu": train_swa_mfu}[spec["reader"]]
    return reader.read(c or ctx(), **spec["args"])


def test_the_cells_shares_sum_to_100_and_the_router_counts_with_the_experts():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", []) and m["name"].startswith("step.train.")]
    parts = [n for n in mine if n not in ("step.train.moe_route_share", "step.train.swa_share")]
    assert sorted(parts) == sorted(["step.train.attn_share", "step.train.head_loss_share",
                                    "step.train.update_share", "step.train.moe_share",
                                    "step.train.unattributed_share.swa"])
    shares = {n: read(n) for n in parts}
    assert abs(sum(shares.values()) - 100.0) < 1e-9, shares
    total = sum(r[2] for r in ROWS)
    assert abs(shares["step.train.attn_share"] - 100 * 0.21 / total) < 1e-9
    assert abs(shares["step.train.moe_share"] - 100 * 0.12 / total) < 1e-9      # the early router included
    assert abs(read("step.train.moe_route_share") - 100 * 0.04 / total) < 1e-9
    assert abs(read("step.train.swa_share") - 100 * 0.14 / total) < 1e-9


def test_the_rooflines_read_the_kernels_by_scope_and_the_grouped_products_once():
    need = costs_swa.train_step(ctx().cfg, 1, 16384, 98304.0)
    assert abs(read("kernel.train.window_attention_roofline")
               - 100.0 * need["window_attention"] / 197e12 / 0.09) < 1e-9      # the two kernels alone
    assert abs(read("kernel.train.gqa_attention_roofline.swa")
               - 100.0 * need["global_attention"] / 197e12 / 0.06) < 1e-9
    experts = max(need["routed_experts"] / 197e12, need["routed_experts_bytes"] / 819e9)
    assert abs(read("kernel.train.moe_experts_roofline.swa") - 100.0 * experts / 0.08) < 1e-9
    assert abs(read("train.swa_mfu") - 100.0 * 30 * need["total"] / (30.0 * 197e12)) < 1e-9


def test_a_program_without_the_scopes_gives_nothing():
    c = ctx()
    c.facts[scope_reduce.FACT] = {"by_scope": {MOD: [["jit(train_step)/ff", "", 1.0]]}}
    for name in ("step.train.swa_share", "kernel.train.window_attention_roofline",
                 "kernel.train.gqa_attention_roofline.swa", "kernel.train.moe_experts_roofline.swa"):
        assert read(name, c) is None, name
    c.facts.pop("moe_pairs_per_step")
    assert read("train.swa_mfu", c) is None


def test_the_band_pairs_against_a_brute_force_count():
    for n, window in ((64, 1), (64, 7), (64, 64), (64, 100), (200, 33), (1000, 256)):
        gap = np.arange(n)[:, None] - np.arange(n)[None, :]
        assert costs_swa.band_pairs(n, window) == int(((gap >= 0) & (gap < window)).sum())
    assert costs_swa.band_pairs(16384, None) == 16384 * 16385 // 2
    assert costs_swa.band_pairs(16384, 4096) / costs_swa.band_pairs(16384, None) < 0.4375


def test_the_costs_follow_the_files_arithmetic():
    cfg = ctx().cfg
    assert costs_swa.windows(cfg) == [None, 4096, 4096, 4096]
    assert costs_swa.attention_params(cfg) == 20_971_520
    assert costs_swa.expert_params(cfg) == 5_898_240
    assert costs_swa.expected_pairs(cfg, 16384) == 4 * 16384 * 6 * 16 / 64           # 1,536 an expert a layer
    need = costs_swa.train_step(cfg, 1, 16384)
    forward = {k: v / 3 for k, v in need.items() if k not in ("total", "routed_experts_bytes", "pairs_here")}
    assert abs(forward["attention_projections"] + forward["routed_experts"] + forward["routers"] - 3.93e12) < 0.01e12
    assert abs(forward["head"] - 3.19e12) < 0.01e12
    assert abs(need["total"] - 34.7e12) < 0.05e12
