"""Required decode work of gqa_dense against counts made by hand."""
import json
from pathlib import Path

from chipbench.work import gqa_dense

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


# an MHA model of MiniCPM-2B's widths (36 KV heads, 9 KB of KV a token
# and layer), 10 layers: the counts do not depend on GQA
MHA = {"n_layers": 10, "d_model": 2304, "n_heads": 36, "n_kv_heads": 36,
       "head_dim": 64, "d_ff": 5760, "vocab": 122753, "qkv_bias": False,
       "topk": 2048, "d_idx": 64, "n_idx_heads": 4}


def test_qwen2_one_slot_1000_entries():
    # per layer: q,k,v,o 11,010,048; indexer projections 995,328 and
    # scores over 1000 keys 520,000; attention over 1001 entries
    # 6,150,144; MLP 82,575,360 -> 101,250,880 x 28 layers, plus the
    # lm_head 466,747,392
    w = gqa_dense.decode_step(model("qwen2-1.5b"), [1000])
    assert w["flops"] == 3_301_772_032
    # weights 94,590,976 B a layer x 28 + lm_head and final norm
    # 466,750,464 + one embedding row 3,072 + per layer 1000 keys
    # (128,000) and 1000 entries (1,024,000) read, one entry and key
    # written (1,152) x 28
    assert w["bytes"] == 3_147_589_120


def test_minicpm_one_slot_5000_entries():
    # per layer: q,k,v,o 42,467,328; indexer 1,492,992 + 2,600,000;
    # attention over the top-2048 + itself 18,883,584; MLP 79,626,240
    # -> 145,070,144 x 10, plus the lm_head 565,645,824
    w = gqa_dense.decode_step(MHA, [5000])
    assert w["flops"] == 2_016_347_264
    # weights 123,595,776 B a layer x 10 + 565,650,432 + 4,608, per
    # layer 5000 keys (640,000), 2048 entries of 9216 B (18,874,368),
    # 9,344 written, x 10
    assert w["bytes"] == 1_996_849_920


def test_weights_are_read_once_per_step():
    m = model("qwen2-1.5b")
    one = gqa_dense.decode_step(m, [1000])
    two = gqa_dense.decode_step(m, [1000, 1000])
    assert two["flops"] == 2 * one["flops"]
    per_slot = 3072 + 28 * 1_153_152
    assert two["bytes"] - one["bytes"] == per_slot


def test_top_k_caps_attention_and_entry_reads():
    m = MHA
    a = gqa_dense.slot_layer_bytes(m, 2048)
    b = gqa_dense.slot_layer_bytes(m, 4096)
    assert b - a == 2048 * m["d_idx"] * 2          # only the keys grow
    assert (gqa_dense.slot_layer_flops(m, 8000)
            - gqa_dense.slot_layer_flops(m, 4000)
            == 2 * 4 * 64 * 4000 + 2 * 4 * 4000)     # only the scan grows
