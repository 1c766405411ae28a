"""The linear-attention cell's readers on a made-up reduction: the six shares
of the step sum to 100, the delta rule's kernels are read by their scope and
name, the grouped products that reach the trace without a scope are counted
by their name, once; and ``costs_gdn`` against the configuration's own
arithmetic."""

import json
import pathlib
import types

from benchmarks import costs, costs_gdn, scope_reduce
from benchmarks.readers import gdn_roofline, scope_kernel_share, scope_share, train_gdn_mfu

ROOT = pathlib.Path(__file__).resolve().parents[2]
MOD = "jit_train_step"
CELL = "train-qwen3next-d4-ep16-s8k"
ROWS = [
    ["jit(train_step)/linattn/mixer_0/linattn.proj/dot", "", 0.20],
    ["jit(train_step)/linattn/mixer_0/linattn.conv/ssm_conv", "ssm_conv_fwd", 0.02],
    ["jit(train_step)/linattn/mixer_0/linattn.delta/gdn", "gdn_chunk_fwd", 0.10],
    ["jit(train_step)/transpose(jvp(linattn))/mixer_0/linattn.delta/gdn", "gdn_chunk_bwd", 0.08],
    ["jit(train_step)/linattn/mixer_0/linattn.delta/l2norm", "", 0.03],
    ["jit(train_step)/linattn/mixer_0/linattn.norm", "", 0.02],
    ["jit(train_step)/attn.gated/mixer_3/flash", "flash_fwd", 0.06],
    ["jit(train_step)/attn.gated/mixer_3/dot", "", 0.05],
    ["jit(train_step)/moe/ff_1/moe.router", "", 0.03],
    ["jit(train_step)/moe/ff_1/moe.experts/convert", "", 0.01],
    ["", "ragged-dot-none", 0.08],                      # the grouped products: no scope
    ["jit(train_step)/head_loss", "", 0.06],
    ["jit(train_step)/embed", "", 0.01],
    ["jit(train_step)/update/update.optimizer", "", 0.04],
    ["", "", 0.02],                                     # nothing names it
]


def ctx():
    return types.SimpleNamespace(
        cfg=costs.load_config("qwen3-next-80b-a3b-d4-ep16"), chips=1, trace=True,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        reduced={"modules": {MOD: {"count": 1}}, "window_s": 1.0},
        facts={"steps": 30, "window_s": 30.0, "rows": 2, "tokens": 8192,
               "moe_pairs_per_step": 40960.0, scope_reduce.FACT: {"by_scope": {MOD: ROWS}}},
    )


def read(name, c=None):
    spec = json.loads((ROOT / "benchmarks" / "metrics" / f"{name}.json").read_text())
    reader = {"scope_share": scope_share, "scope_kernel_share": scope_kernel_share,
              "gdn_roofline": gdn_roofline, "train_gdn_mfu": train_gdn_mfu}[spec["reader"]]
    return reader.read(c or ctx(), **spec["args"])


def test_the_cells_shares_sum_to_100():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m["workloads"] and m["name"].startswith("step.train.")]
    parts = [n for n in mine if n not in ("step.train.moe_route_share", "step.train.delta_rule_share")]
    shares = {n: read(n) for n in parts}
    assert abs(sum(shares.values()) - 100.0) < 1e-9, shares
    total = sum(r[2] for r in ROWS)
    assert abs(shares["step.train.linattn_share"] - 100 * 0.45 / total) < 1e-9
    assert abs(shares["step.train.moe_share"] - 100 * 0.12 / total) < 1e-9       # 0.03 + 0.01 + 0.08
    assert abs(shares["step.train.unattributed_share.gdn"] - 100 * 0.02 / total) < 1e-9
    assert abs(read("step.train.delta_rule_share") - 100 * 0.21 / total) < 1e-9   # kernels and the rest of the scope


def test_the_rooflines_read_the_kernels_by_scope_and_the_grouped_products_once():
    need = costs_gdn.train_step(ctx().cfg, 2, 8192, 40960.0)
    delta = max(need["delta_rule"] / 197e12, need["delta_rule_bytes"] / 819e9)
    assert abs(read("kernel.train.delta_rule_roofline") - 100.0 * delta / 0.18) < 1e-9   # the two kernels alone
    assert need["delta_rule_bytes"] / 819e9 > need["delta_rule"] / 197e12               # bound by bytes
    assert abs(read("kernel.train.gated_attention_roofline")
               - 100.0 * need["attention"] / 197e12 / 0.06) < 1e-9
    experts = max(need["routed_experts"] / 197e12, need["routed_experts_bytes"] / 819e9)
    assert abs(read("kernel.train.moe_experts_roofline.gdn") - 100.0 * experts / 0.09) < 1e-9
    assert abs(read("train.gdn_mfu") - 100.0 * 30 * need["total"] / (30.0 * 197e12)) < 1e-9


def test_a_program_without_the_scopes_gives_nothing():
    c = ctx()
    c.facts[scope_reduce.FACT] = {"by_scope": {MOD: [["jit(train_step)/ff", "", 1.0]]}}
    for name in ("step.train.linattn_share", "step.train.delta_rule_share",
                 "kernel.train.delta_rule_roofline", "kernel.train.gated_attention_roofline",
                 "kernel.train.moe_experts_roofline.gdn"):
        assert read(name, c) is None, name
    c.facts.pop("moe_pairs_per_step")
    assert read("train.gdn_mfu", c) is None


def test_the_costs_follow_the_files_arithmetic():
    cfg = ctx().cfg
    assert costs_gdn.layer_kinds(cfg) == ["linear_attention"] * 3 + ["full_attention"]
    assert costs_gdn.linattn_params(cfg) == 2048 * 12288 + 2048 * 64 + 4096 * 2048      # 33.7 M
    assert costs_gdn.attention_params(cfg) == 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048  # 27.3 M
    assert costs_gdn.expert_params(cfg) == 3 * 2048 * 512
    assert costs_gdn.expected_pairs(cfg, 16384) == 4 * 16384 * 10 * 32 / 512            # 320 an expert a layer
    need = costs_gdn.train_step(cfg, 2, 8192)
    assert 22.5e12 < need["total"] < 23.0e12
    terms = costs_gdn.delta_rule_flops_forward(cfg, 8192)
    assert abs(sum(terms.values()) * 2 * 3 * 3 - need["delta_rule"]) < 1.0
    # one chunk of one value head: three (64 x 128 x 128) products, two triangles
    # over 128 channels, the inverse, and half a key head's two triangles
    per = sum(terms.values()) / (128 * 32)
    assert abs(per - (3 * 2 * 64 * 128 * 128 + 2 * 2 * 2080 * 128 + 64**3 / 3
                      + (2 * 2016 + 2 * 2080) * 128 / 2)) < 1e-6
