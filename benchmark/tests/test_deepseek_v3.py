"""The deepseek_v3 architecture file: its counts to the byte at
Moonlight-16B-A3B's published widths, the configuration file against
the catalog row it was copied from, and the reader of the latent
decode kernel's roofline share on a made-up cut."""

import json
import os
import types

import pytest

from benchmark import architectures, run
from benchmark.readers import op_roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = run.load_json(ROOT, "benchmark", "configs",
                       "text-moonlight-16b-a3b.json")
FAMILY = architectures.load(CONFIG, [run.HERE])

# The catalog row's ``config`` (model-configs guide, Moonlight-16B-A3B).
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}


def test_the_file_holds_every_published_key_to_the_letter():
    differ = {key for key, value in PUBLISHED.items()
              if CONFIG.get(key, "absent") != value}
    assert differ == {"num_hidden_layers"} == set(CONFIG["reduced"])
    assert CONFIG["source_values"] == {"num_hidden_layers": 27}
    assert CONFIG["num_hidden_layers"] == 9
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = next(entry for entry in manifest["configs"]
                 if entry["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    served = CONFIG["definition"]["elements"][0]["parameters"]
    assert served["max_seq"] == CONFIG["max_position_embeddings"] == 8192
    assert "quantize" not in served


def test_element_parameters_are_the_family_and_its_widths():
    handed = FAMILY.element_parameters(CONFIG)
    assert handed["family"] == "deepseek_v3"
    assert set(handed["widths"]) == set(FAMILY.WIDTH_FIELDS)
    assert handed["widths"]["num_hidden_layers"] == 9
    from aiko_services_tpu.models.families import FAMILY_WIDTHS
    assert FAMILY.WIDTH_FIELDS == FAMILY_WIDTHS["deepseek_v3"]


def test_width_differences_against_a_served_config():
    from aiko_services_tpu.models.deepseek import DeepseekConfig
    served = DeepseekConfig.from_widths(
        FAMILY.element_parameters(CONFIG)["widths"], max_seq=8192)
    batcher = types.SimpleNamespace(config=served)
    assert FAMILY.width_differences(CONFIG, batcher) == []
    assert FAMILY.published_widths(served)["kv_lora_rank"] == 512
    import dataclasses
    wrong = types.SimpleNamespace(config=dataclasses.replace(
        served, n_experts=32, max_seq=4096))
    assert {key for key, _, _ in FAMILY.width_differences(
        CONFIG, wrong)} == {"n_routed_experts", "max_position_embeddings"}
    assert ("scoring_func", "softmax", "sigmoid") in \
        FAMILY.width_differences({**CONFIG, "scoring_func": "softmax"},
                                 batcher)


ATTENTION = 13_762_560      # W_q 6,291,456 + W_kva 1,179,648
#                             + W_kvb 2,097,152 + W_o 4,194,304
ROUTER, SHARED, EXPERT = 131_072, 17_301_504, 8_650_752
DENSE = ATTENTION + 69_206_016
HEAD = 335_544_320
ROW = 576 * 2 * 9           # one token's latent rows, nine layers


def test_the_pieces_of_the_count():
    assert FAMILY.attention_weights(CONFIG) == ATTENTION
    assert FAMILY.expert_weights(CONFIG) == EXPERT
    assert FAMILY.cache_bytes_per_token(CONFIG) == ROW == 10_368
    assert FAMILY.experts_touched(CONFIG, 0) == 0.0
    assert FAMILY.experts_touched(CONFIG, 1) == pytest.approx(6.0)
    assert FAMILY.experts_touched(CONFIG, 29) == pytest.approx(
        64 * (1 - (58 / 64) ** 29))
    assert 59.0 < FAMILY.experts_touched(CONFIG, 29) < 61.0
    assert FAMILY.experts_touched(CONFIG, 10_000) == 64.0


@pytest.mark.parametrize("rows,context", [(1, 0), (29, 3200), (32, 7296)])
def test_a_decode_step_to_the_byte(rows, context):
    work = FAMILY.decode_step(CONFIG, rows, context)
    touched = 64 * (1 - (58 / 64) ** rows)
    assert work["bytes"] == pytest.approx(
        2 * (8 * (ATTENTION + ROUTER + SHARED + touched * EXPERT)
             + DENSE + HEAD) + rows * context * ROW, rel=1e-12)
    assert work["operations"] == pytest.approx(
        2.0 * rows * (8 * (ATTENTION + ROUTER + SHARED + 6 * EXPERT)
                      + DENSE + HEAD)
        + rows * context * 9 * 16 * (2 * 576 + 2 * 512), rel=1e-12)


def test_a_full_step_streams_what_the_issue_reckoned():
    # ~60 of 64 experts in 8 layers, the dense layer, the head, and
    # 29 x 3.2 k latent rows: ~9.7 GB of weights and ~1.0 GB of cache.
    work = FAMILY.decode_step(CONFIG, 29, 3200)
    cache = 29 * 3200 * ROW
    assert cache == 962_150_400
    assert 9.6e9 < work["bytes"] - cache < 9.8e9
    kernel = FAMILY.latent_decode_attention(CONFIG, 29, 3200)
    assert kernel["bytes"] == cache
    assert kernel["operations"] == 29 * 3200 * 9 * 16 * 2176


def _context(ops, slice_steps=8):
    """A reader's context over a made-up cut: ``ops`` are (name,
    start_ns, duration_ns) of one device's op line."""
    notes = {}
    return types.SimpleNamespace(
        architecture=FAMILY, config=CONFIG, notes=notes,
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        cut={"devices": {"/device:TPU:0": {"modules": [], "ops": ops}},
             "host": []},
        slice_counters={"batcher.steps": slice_steps},
        counters={"client.answered": 100,
                  "batcher.prefill_tokens": 313_600},
        workload={"new_tokens": 128},
        metric=lambda name: {"batcher.rows_per_step": 29.0}[name])


ARGS = json.load(open(os.path.join(
    ROOT, "benchmark", "layer_metrics",
    "kernel.mla_decode_roofline.json")))["args"]


def test_the_kernel_share_from_a_cut():
    # 72 calls (8 steps x 9 layers) of 0.2 ms inside a while of 20 ms.
    ops = [["%while.1 = while(...)", 0, 20_000_000]] + [
        [f"%latent_decode_attention_paged.{index % 3} = custom-call(...)",
         1000 + index * 250_000, 200_000] for index in range(72)]
    context = _context(ops)
    share = op_roofline.read(ARGS, context)
    rows_context = 313_600 / 100 + 64
    least = 29 * rows_context * ROW / 819e9          # memory-bound
    assert share == pytest.approx(100 * least * 8 / (72 * 0.2e-3))
    assert 0 < share < 100
    noted = context.notes["latent_decode_attention"]
    assert noted["bound"] == "memory" and noted["steps"] == 8


def test_the_kernel_share_is_silent_without_the_kernel():
    # the parent's programs have no such op: nothing, not an error
    assert op_roofline.read(ARGS, _context(
        [["%fusion.1 = fusion(...)", 0, 1000]])) is None
    no_count = _context([])
    no_count.architecture = types.SimpleNamespace()
    assert op_roofline.read(ARGS, no_count) is None
    untraced = _context([])
    untraced.cut = None
    assert op_roofline.read(ARGS, untraced) is None
