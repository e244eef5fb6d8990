"""The harness's check of a configuration file against the program: every
key of the file's ``model`` block is read from the program's configuration
and compared, and the reference and the work count get the block's keys,
at the tiny configuration's values in the CPU rehearsal."""
import dataclasses
import importlib

import pytest

# the qwen2-1.5b file's model block (what the reference and the work count
# get on the chip), and what they get in the CPU rehearsal
QWEN2 = {"n_layers": 28, "d_model": 1536, "n_heads": 12, "n_kv_heads": 2,
         "head_dim": 128, "d_ff": 8960, "vocab": 151936, "qkv_bias": True,
         "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
         "tie_word_embeddings": False, "topk": 2048, "d_idx": 64,
         "n_idx_heads": 4}
QWEN2_REDUCED = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                 "head_dim": 16, "d_ff": 128, "vocab": 256, "qkv_bias": True,
                 "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
                 "tie_word_embeddings": False, "topk": 64, "d_idx": 8,
                 "n_idx_heads": 2}
REHEARSAL = {"topk": 64}
# the MLA and MoE widths of the registry's deepseek-v32, as published
DEEPSEEK = {"n_layers": 61, "d_model": 7168, "n_heads": 128,
            "head_dim": 128, "vocab": 129280, "kv_lora_rank": 512,
            "q_lora_rank": 1536, "qk_rope_dim": 64, "n_experts": 256,
            "topk_experts": 8, "topk": 2048, "d_idx": 128, "n_idx_heads": 64,
            "rms_norm_eps": 1e-06, "tie_word_embeddings": False}


@pytest.fixture(scope="module")
def run_module():
    return importlib.import_module("chipbench.run")


def conf(arch, model, name="c"):
    return {"name": name, "arch": arch, "overrides": {}, "model": dict(model)}


def test_qwen2_sizes_are_unchanged_in_both_modes(run_module):
    cfg, sizes = run_module.model_config(conf("qwen2-1.5b", QWEN2), None)
    assert sizes == QWEN2 and list(sizes) == list(QWEN2)
    assert {k: type(v) for k, v in sizes.items()} == {
        k: type(v) for k, v in QWEN2.items()}
    cfg, sizes = run_module.model_config(conf("qwen2-1.5b", QWEN2), REHEARSAL)
    assert sizes == QWEN2_REDUCED and list(sizes) == list(QWEN2_REDUCED)
    assert {k: type(v) for k, v in sizes.items()} == {
        k: type(v) for k, v in QWEN2_REDUCED.items()}
    assert cfg.sac.topk == 64 and cfg.n_layers == 2


@pytest.mark.parametrize("reduced", [None, REHEARSAL], ids=["full", "reduced"])
def test_a_key_the_program_lacks_stops_the_run(run_module, reduced):
    model = {**QWEN2, "moe_intermediate_size": 2048, "n_group": 8}
    with pytest.raises(SystemExit, match="moe_intermediate_size") as e:
        run_module.model_config(conf("qwen2-1.5b", model, "q"), reduced)
    assert "n_group" in str(e.value) and str(e.value).startswith("q:")


@pytest.mark.parametrize("key,value", [("d_ff", 8192), ("head_dim", 64),
                                       ("qkv_bias", False),
                                       ("tie_word_embeddings", True),
                                       ("rms_norm_eps", 1e-5),
                                       ("n_idx_heads", 8)])
def test_a_value_the_program_does_not_run_stops_the_run(run_module, key,
                                                        value):
    with pytest.raises(SystemExit, match=f"'{key}'"):
        run_module.model_config(conf("qwen2-1.5b", {**QWEN2, key: value}),
                                None)


def test_mla_and_moe_widths_pass_and_reach_the_reference(run_module):
    cfg, sizes = run_module.model_config(conf("deepseek-v32", DEEPSEEK), None)
    assert sizes == DEEPSEEK
    cfg, sizes = run_module.model_config(conf("deepseek-v32", DEEPSEEK),
                                         REHEARSAL)
    assert list(sizes) == list(DEEPSEEK)
    assert [sizes[k] for k in ("kv_lora_rank", "q_lora_rank", "qk_rope_dim",
                               "n_experts", "topk_experts")] == [
        32, 48, 16, 4, 2]
    assert (sizes["d_model"], sizes["topk"], sizes["head_dim"]) == (64, 64, 16)


def test_a_field_the_program_has_takes_precedence_over_the_table(run_module):
    """``head_dim`` is a field the registry leaves None for qwen2: the
    table gives the width the program derives; where set, the field."""
    from repro.configs import get_config
    qwen2 = get_config("qwen2-1.5b")
    assert qwen2.head_dim is None
    assert run_module.program_value(qwen2, "head_dim") == 128
    other = dataclasses.replace(qwen2, head_dim=96)
    assert run_module.program_value(other, "head_dim") == 96
    with pytest.raises(KeyError):
        run_module.program_value(qwen2, "n_routed_experts")
