"""The DeepSeek-V3.2 cell's files: its configuration against the program
in both modes, its required decode work against counts made by hand, and
its readers on made-up runs."""
import importlib
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench.metrics import (dsv32_decode_roofline, dsv32_decode_step_ms,
                               dsv32_moe_ms, dsv32_moe_roofline,
                               dsv32_prefill_ms_per_ktok)
from chipbench.work import mla_moe

ROOT = Path(__file__).resolve().parents[2]
CONF = json.loads((ROOT / "chipbench/configs/deepseek-v32.json").read_text())
CATALOG = CONF["published"]


@pytest.fixture(scope="module")
def run_module():
    return importlib.import_module("chipbench.run")


def test_file_states_the_published_config_and_its_cut():
    """Every key of the published config at the top level as run, the
    changed ones listed in ``reduced``; ``rope_scaling`` whole."""
    changed = {k for k, v in CATALOG.items() if CONF[k] != v}
    assert changed == set(CONF["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"}
    assert (CONF["num_hidden_layers"], CONF["n_routed_experts"],
            CONF["vocab_size"]) == (7, 8, 16160)
    assert CONF["rope_scaling"] == CATALOG["rope_scaling"]
    m = CONF["model"]
    assert m["n_layers"] == CONF["num_hidden_layers"]
    assert m["first_k_dense"] == CATALOG["first_k_dense_replace"]
    assert m["n_experts"] == CATALOG["n_routed_experts"]
    assert m["n_experts_held"] == CONF["n_routed_experts"]
    assert m["vocab"] == CONF["vocab_size"]
    assert (m["d_ff"], m["d_ff_dense"]) == (CATALOG["moe_intermediate_size"],
                                            CATALOG["intermediate_size"])
    assert (m["d_idx"], m["n_idx_heads"], m["topk"]) == (
        CATALOG["index_head_dim"], CATALOG["index_n_heads"],
        CATALOG["index_topk"])
    assert m["rope_factor"] == CATALOG["rope_scaling"]["factor"]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in bench["configs"]}["deepseek-v32"]
    assert entry["reduced"] == CONF["reduced"]
    assert entry["source"] == CONF["source"]


def test_model_block_is_what_the_program_runs(run_module):
    cfg, sizes = run_module.model_config(CONF, None)
    assert sizes == CONF["model"]
    assert (cfg.n_layers, cfg.experts_held, cfg.vocab) == (7, 8, 16160)


def test_rehearsal_gets_the_tiny_share(run_module):
    cell = json.loads((ROOT / "chipbench/workloads/deepseek-v32.longdoc.json"
                       ).read_text())
    cfg, sizes = run_module.model_config(CONF, cell["reduced"])
    assert list(sizes) == list(CONF["model"])
    assert (sizes["n_layers"], sizes["first_k_dense"]) == (3, 1)
    assert (sizes["n_experts"], sizes["n_experts_held"],
            sizes["expert_offset"]) == (4, 2, 1)
    assert sizes["n_expert_groups"] == 2 and sizes["topk"] == 64
    assert sizes["d_model"] == 64 and sizes["vocab"] == 256


def test_a_changed_width_stops_the_run(run_module):
    conf = dict(CONF, model={**CONF["model"], "d_ff_dense": 16384})
    with pytest.raises(SystemExit, match="d_ff_dense"):
        run_module.model_config(conf, None)


M = CONF["model"]


def test_one_slot_1000_entries():
    # per slot and layer: MLA projections 2*(7168*1536 + 1536*24576 +
    # 7168*576 + 128*128*512 * 2 + 16384*7168) = 374,210,560; attention
    # over 1001 latents 2*128*576*1001 + 2*128*512*1001 = 278,806,528;
    # indexer 2*(1536*8192 + 7168*128 + 7168*64) + 2*64*128*1000 +
    # 2*64*1000 = 44,430,336 -> 697,447,424 x 7; lm_head 2*7168*16160
    w = mla_moe.decode_step(M, [1000])
    attn = 7 * 697_447_424 + 231_669_760
    dense = 3 * 6 * 7168 * 18432
    # one token: router 2*7168*256, shared 6*7168*2048, 8*8/256 routed
    # assignments of 6*7168*2048
    moe = 4 * (3_670_016 + 88_080_384 + 88_080_384 / 4)
    assert w["moe_flops"] == pytest.approx(moe)
    assert w["flops"] == pytest.approx(attn + dense + moe)
    # held experts read: 8 * (1 - (1 - 8/256)) = 0.25 of one expert's
    # 88,080,384 B, plus router 3,670,016 B and its bias 1,024 B and the
    # shared expert 88,080,384 B, x 4 layers
    moe_bytes = 4 * (0.25 * 88_080_384 + 3_670_016 + 1024 + 88_080_384)
    assert w["moe_bytes"] == pytest.approx(moe_bytes)
    per_layer = mla_moe.attn_weight_bytes(M)
    assert per_layer == 2 * (7168 * 1536 + 1536 + 1536 * 24576
                             + 7168 * 576 + 512 + 2 * 512 * 16384
                             + 16384 * 7168 + 1536 * 8192 + 7168 * 128
                             + 256 + 7168 * 64 + 2 * 7168)
    kv = 1000 * 256 + 1000 * 1152 + 1152 + 256
    assert w["bytes"] == pytest.approx(
        7 * per_layer + 3 * 3 * 7168 * 18432 * 2 + (7168 * 16160 + 7168) * 2
        + 7168 * 2 + 7 * kv + moe_bytes)


def test_held_experts_read_are_those_routing_reaches():
    one = mla_moe.expert_layer(M, 1)
    four = mla_moe.expert_layer(M, 4)
    expert = 3 * 7168 * 2048 * 2
    fixed = (7168 * 256 + 3 * 7168 * 2048) * 2 + 256 * 4
    assert one["bytes"] - fixed == pytest.approx(0.25 * expert)
    assert four["bytes"] - fixed == pytest.approx(
        8 * (1 - (1 - 8 / 256) ** 4) * expert)
    assert four["flops"] == pytest.approx(4 * one["flops"])
    assert mla_moe.expert_layer(M, 0) == {"flops": 0, "bytes": fixed}


def run_of(scope_ms, n=2, moe=True):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    work = [{"flops": 1e9, "bytes": 8e9, "moe_flops": 4e8,
             "moe_bytes": 8.19e8}] * n
    if not moe:
        work = [{"flops": 1e9, "bytes": 8e9}] * n
    ex = [(0, 20_000_000), (30_000_000, 50_000_000)]
    return SimpleNamespace(scope_ms=scope_ms, step_work=work, peaks=peaks,
                           trace=SimpleNamespace(executions=lambda m: ex),
                           prefills=[], t0=0, t1=1)


def test_expert_layer_readers():
    run = run_of({"router": 0.5, "experts": 3.0, "shared_expert": 0.5,
                  "attention": 9.0})
    assert dsv32_moe_ms.read(run) == pytest.approx(4.0)
    # the least time 8.19e8 B / 819e9 B/s = 1 ms over 4 ms
    assert dsv32_moe_roofline.read(run) == pytest.approx(25.0)
    assert dsv32_moe_ms.read(run_of({"mlp": 3.0})) is None
    assert dsv32_moe_roofline.read(run_of({"mlp": 3.0})) is None
    assert dsv32_moe_roofline.read(
        run_of({"experts": 2.0}, moe=False)) is None
    assert dsv32_moe_ms.read(run_of(None)) is None


def test_renamed_readers_read_as_their_originals():
    run = run_of({})
    assert dsv32_decode_step_ms.read(run) == pytest.approx(20.0)
    # 8e9 B / 819e9 B/s = 9.768 ms over 20 ms
    assert dsv32_decode_roofline.read(run) == pytest.approx(
        100 * 8e9 / 819e9 / 0.020)
    assert dsv32_prefill_ms_per_ktok.read(run) is None


def test_the_cell_is_sized_for_the_share():
    cell = json.loads((ROOT / "chipbench/workloads/deepseek-v32.longdoc.json"
                       ).read_text())
    assert (cell["slots"], cell["max_ctx"], cell["clients"]) == (4, 8192, 4)
    assert cell["traffic"] == "longdoc"
    small = cell["reduced"]
    # in the rehearsal each client finishes its short requests and then
    # holds a long one past any window, so the requests compared do not
    # depend on the host's speed
    assert max(o for _, o, _ in small["requests"]) + 64 > 2000
    assert all(p + o <= small["max_ctx"] for p, o, _ in small["requests"])
