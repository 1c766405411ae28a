"""The routed-expert cell's readers on a made-up reduction: the grouped
products that reach the trace without a scope are counted by their name, once,
and the seven shares of the step sum to 100."""

import json
import pathlib
import types

from benchmarks import costs, costs_moe, scope_reduce
from benchmarks.readers import moe_roofline, scope_kernel_share, scope_share

ROOT = pathlib.Path(__file__).resolve().parents[2]
MOD = "jit_train_step"
ROWS = [
    ["jit(train_step)/attn.mla/mixer_0/flash", "flash_fwd", 0.30],
    ["jit(train_step)/attn.mla/mixer_0/dot", "", 0.20],
    ["jit(train_step)/nextn/attn.mla/mixer_0/dot", "", 0.05],
    ["jit(train_step)/ff/ff_0", "", 0.05],
    ["jit(train_step)/moe/ff_1/moe.router", "", 0.02],
    ["jit(train_step)/moe/ff_1/moe.experts/convert", "", 0.01],
    ["", "ragged-dot-none", 0.08],                      # the grouped products: no scope
    ["jit(train_step)/mtp/eh_proj", "", 0.01],
    ["jit(train_step)/head_loss", "", 0.06],
    ["jit(train_step)/embed", "", 0.01],
    ["jit(train_step)/update/update.optimizer", "", 0.04],
    ["", "", 0.02],                                     # nothing names it
]


def ctx():
    c = types.SimpleNamespace(
        cfg=costs.load_config("joyai-llm-flash-d6-ep16"), chips=1, trace=True,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        reduced={"modules": {MOD: {"count": 1}}, "window_s": 1.0},
        facts={"steps": 30, "window_s": 30.0, "rows": 4, "tokens": 4096,
               "moe_pairs_per_step": 49152.0, scope_reduce.FACT: {"by_scope": {MOD: ROWS}}},
    )
    return c


def metric_args(name):
    return json.loads((ROOT / "benchmarks" / "metrics" / f"{name}.json").read_text())


def read(name):
    spec = metric_args(name)
    reader = {"scope_share": scope_share, "scope_kernel_share": scope_kernel_share,
              "moe_roofline": moe_roofline}[spec["reader"]]
    return reader.read(ctx(), **spec["args"])


def test_the_seven_shares_sum_to_100_and_the_grouped_products_count_as_moe():
    shares = {n: read(f"step.train.{n}") for n in (
        "attn_share", "ff_share", "head_loss_share", "update_share", "moe_share", "mtp_share",
        "unattributed_share.moe")}
    assert abs(sum(shares.values()) - 100.0) < 1e-9, shares
    total = sum(r[2] for r in ROWS)
    assert abs(shares["moe_share"] - 100 * 0.11 / total) < 1e-9          # 0.02 + 0.01 + 0.08
    assert abs(shares["unattributed_share.moe"] - 100 * 0.02 / total) < 1e-9
    assert abs(shares["mtp_share"] - 100 * 0.01 / total) < 1e-9          # the module's own part only
    assert abs(read("step.train.moe_route_share") - 100 * 0.02 / total) < 1e-9


def test_the_experts_roofline_reads_the_grouped_products_once():
    need = costs_moe.train_step(ctx().cfg, 4, 4096, 49152.0)
    least = max(need["routed_experts"] / 197e12, need["routed_experts_bytes"] / 819e9)
    want = 100.0 * (1.0 / 1.0) * least / 0.09                            # one step in the slice
    assert abs(read("kernel.train.moe_experts_roofline") - want) < 1e-9
    assert read("kernel.train.mla_attention_roofline") > 0


def test_a_program_without_the_scopes_gives_nothing():
    c = ctx()
    c.facts[scope_reduce.FACT] = {"by_scope": {MOD: [["jit(train_step)/ff", "", 1.0]]}}
    assert scope_kernel_share.read(c, MOD, scopes=["moe"], kernels=["ragged-dot*"]) is None
    c.facts.pop("moe_pairs_per_step")
    assert moe_roofline.read(c, MOD, "experts") is None
