"""The KDA / latent-attention cell's readers on a made-up reduction: the six
shares of the step sum to 100, the KDA rule's kernels are read by their scope
(the gates' and the projections' operations under ``linattn`` count with the
mixer, not with the rule), the dense layer counts under ``ff``, the grouped
products that reach the trace without a scope are counted by their name,
once; and ``costs_kda`` against the configuration's own arithmetic."""

import json
import pathlib
import types

from benchmarks import costs, costs_kda, scope_reduce
from benchmarks.readers import kda_roofline, scope_kernel_share, scope_share, train_kda_mfu

ROOT = pathlib.Path(__file__).resolve().parents[2]
MOD = "jit_train_step"
CELL = "train-kimilinear-d5-ep32-s16k"
ROWS = [
    ["jit(train_step)/linattn/transformer/mixer_0/fn/linattn.proj/dot", "", 0.10],
    ["jit(train_step)/linattn/transformer/mixer_0/fn/linattn.gate/dot", "", 0.02],
    ["jit(train_step)/linattn/transformer/mixer_0/fn/linattn.conv/ssm_conv", "ssm_conv_fwd", 0.01],
    ["jit(train_step)/linattn/transformer/mixer_0/fn/linattn.kda/kda", "kda_chunk_tables", 0.06],
    ["jit(train_step)/linattn/transformer/mixer_0/fn/linattn.kda/kda", "kda_chunk_fwd", 0.03],
    ["jit(train_step)/transpose(jvp(linattn))/mixer_0/fn/linattn.kda/kda", "kda_chunk_bwd", 0.09],
    ["jit(train_step)/linattn/transformer/mixer_0/fn/linattn.kda/l2norm", "", 0.01],
    ["jit(train_step)/linattn/transformer/mixer_0/fn/linattn.norm", "", 0.01],
    ["jit(train_step)/attn.mla/transformer/mixer_3/fn/flash", "flash_fwd", 0.03],
    ["jit(train_step)/transpose(jvp(attn.mla))/mixer_3/fn/flash", "flash_bwd", 0.05],
    ["jit(train_step)/ff/transformer/ff_0/fn/Dense_0/dot", "", 0.04],
    ["jit(train_step)/moe/ff_1/fn/moe.router/top_k", "", 0.02],
    ["jit(train_step)/moe/ff_1/fn/moe.experts/convert", "", 0.01],
    ["", "ragged-dot-none", 0.03],                      # the grouped products: no scope
    ["jit(train_step)/head_loss", "", 0.04],
    ["jit(train_step)/embed", "", 0.01],
    ["jit(train_step)/update/update.optimizer", "", 0.03],
    ["", "", 0.02],                                     # nothing names it
]


def ctx():
    return types.SimpleNamespace(
        cfg=costs.load_config("kimi-linear-48b-a3b-d5-ep32"), chips=1, trace=True,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        reduced={"modules": {MOD: {"count": 1}}, "window_s": 1.0},
        facts={"steps": 30, "window_s": 30.0, "rows": 1, "tokens": 16384,
               "moe_pairs_per_step": 16384.0, scope_reduce.FACT: {"by_scope": {MOD: ROWS}}},
    )


def read(name, c=None):
    spec = json.loads((ROOT / "benchmarks" / "metrics" / f"{name}.json").read_text())
    reader = {"scope_share": scope_share, "scope_kernel_share": scope_kernel_share,
              "kda_roofline": kda_roofline, "train_kda_mfu": train_kda_mfu}[spec["reader"]]
    return reader.read(c or ctx(), **spec["args"])


def test_the_cells_shares_sum_to_100():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mine = [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", []) and m["name"].startswith("step.train.")]
    parts = [n for n in mine if n not in ("step.train.moe_route_share", "step.train.kda_share")]
    assert sorted(parts) == sorted([
        "step.train.attn_share", "step.train.ff_share", "step.train.head_loss_share",
        "step.train.update_share", "step.train.moe_share", "step.train.linattn_share",
        "step.train.unattributed_share.kda",
    ])
    shares = {n: read(n) for n in parts}
    assert abs(sum(shares.values()) - 100.0) < 1e-9, shares
    total = sum(r[2] for r in ROWS)
    assert abs(shares["step.train.linattn_share"] - 100 * 0.33 / total) < 1e-9
    assert abs(shares["step.train.attn_share"] - 100 * 0.08 / total) < 1e-9
    assert abs(shares["step.train.ff_share"] - 100 * 0.04 / total) < 1e-9
    assert abs(shares["step.train.moe_share"] - 100 * 0.06 / total) < 1e-9       # 0.02 + 0.01 + 0.03
    assert abs(shares["step.train.unattributed_share.kda"] - 100 * 0.02 / total) < 1e-9
    assert abs(read("step.train.kda_share") - 100 * 0.19 / total) < 1e-9          # kernels and the norms


def test_the_roofline_reads_the_three_kernels_alone_and_the_mfu_the_whole_step():
    need = costs_kda.train_step(ctx().cfg, 1, 16384, 16384.0)
    least = max(need["kda"] / 197e12, need["kda_bytes"] / 819e9)
    assert need["kda_bytes"] / 819e9 > need["kda"] / 197e12                        # bound by bytes
    assert abs(read("kernel.train.kda_roofline") - 100.0 * least / 0.18) < 1e-9
    assert abs(read("train.kda_mfu") - 100.0 * 30 * need["total"] / (30.0 * 197e12)) < 1e-9


def test_the_attention_and_expert_rooflines_read_their_own_kernels():
    need = costs_kda.train_step(ctx().cfg, 1, 16384, 16384.0)
    attention = need["attention"] / 197e12
    assert abs(read("kernel.train.mla_attention_roofline.kda") - 100.0 * attention / 0.08) < 1e-9
    experts = max(need["routed_experts"] / 197e12, need["routed_experts_bytes"] / 819e9)
    # moe.experts' own 0.01 and the unscoped ragged-dot's 0.03; the router is not the experts'
    assert abs(read("kernel.train.moe_experts_roofline.kda") - 100.0 * experts / 0.04) < 1e-9


def test_a_program_without_the_scopes_gives_nothing():
    c = ctx()
    c.facts[scope_reduce.FACT] = {"by_scope": {MOD: [["jit(train_step)/attn.gqa", "", 1.0]]}}
    for name in ("step.train.kda_share", "kernel.train.kda_roofline",
                 "kernel.train.mla_attention_roofline.kda"):
        assert read(name, c) is None, name
    c.facts.pop("moe_pairs_per_step")
    assert read("train.kda_mfu", c) is None
    c.reduced = None
    assert read("kernel.train.kda_roofline", c) is None


def test_the_costs_follow_the_files_arithmetic():
    cfg = ctx().cfg
    assert costs_kda.layer_kinds(cfg) == ["kda", "kda", "kda", "mla", "kda"]
    assert costs_kda.kda_params(cfg) == 39_514_272
    assert costs_kda.mla_params(cfg) == 29_114_880
    assert costs_kda.expert_params(cfg) == 7_077_888
    assert costs_kda.param_count(cfg) == 602_435_456
    assert costs_kda.expected_pairs(cfg, 16384) == 4 * 16384 * 8 * 8 / 256          # 512 an expert a layer
    need = costs_kda.train_step(cfg, 1, 16384)
    assert abs(need["total"] - 42.09e12) < 0.01e12
    assert abs(need["kda_bytes"] - 3 * 4 * 16384 * (4 * 4096 * 2 + 4 * 4096 + 4 * 32)) < 1
    pairs = costs_kda.expected_pairs(cfg, 16384)
    assert abs(need["routed_experts_bytes"] - 3 * 2 * (4 * 8 * 7_077_888 + 2 * pairs * 2304)) < 1
