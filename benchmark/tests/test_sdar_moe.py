"""The sdar_moe architecture file: its counts by hand at
SDAR-30B-A3B-Chat's published widths (ISSUE 36's arithmetic), the
configuration file against the catalog row it was copied from, the
manifest's new files, the new roofline share on a made-up cut, the
reference check's two controls at a small size, and the cell's walk on
the CPU."""

import dataclasses
import json
import os
import subprocess
import sys
import types

import pytest

from benchmark import architectures, run
from benchmark.readers import op_roofline

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = run.load_json(ROOT, "benchmark", "configs",
                       "text-sdar-30b-a3b.json")
FAMILY = architectures.load(CONFIG, [run.HERE])

# The catalog row's ``config`` (model-configs guide, SDAR-30B-A3B-Chat).
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def served_slots():
    return CONFIG["definition"]["elements"][0]["parameters"]["max_slots"]


def test_the_file_holds_every_published_key_to_the_letter():
    differ = {key for key, value in PUBLISHED.items()
              if CONFIG.get(key, "absent") != value}
    assert differ == {"num_hidden_layers", "max_position_embeddings"} \
        == set(CONFIG["reduced"])
    assert CONFIG["source_values"] == {
        key: PUBLISHED[key] for key in CONFIG["reduced"]}
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert CONFIG["num_hidden_layers"] == 8       # depth alone is cut
    assert set(CONFIG["generation"]) == {"block_length", "denoising_steps",
                                         "mask_token"}
    checked = CONFIG["reference"]
    assert len(checked["loop_prompt_tokens"]) >= 12 <= served_slots()
    assert {length % 4 for length in checked["loop_prompt_tokens"]} \
        == {0, 1, 2, 3}
    assert {"query_key_norm", "block_length", "mask_token",
            "decoding_rule", "weights", "serving_precision"} \
        <= set(CONFIG["assumed"])
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = next(entry for entry in manifest["configs"]
                 if entry["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] \
        == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/" \
           "config.json"
    served = CONFIG["definition"]["elements"][0]["parameters"]
    assert served["max_seq"] == CONFIG["max_position_embeddings"] == 1280
    assert served["kv_pages"] == served["max_slots"] * 10 + 1 == 321
    assert "quantize" not in served
    assert {key: served[key] for key in ("block_length",
                                         "denoising_steps")} \
        == {key: CONFIG["generation"][key]
            for key in ("block_length", "denoising_steps")} \
        == {"block_length": 4, "denoising_steps": 2}
    assert served["decode_block_tokens"] % served["block_length"] == 0
    assert "no block's K/V enters the cache" in CONFIG["guarantees"]


def test_the_manifests_new_files_load():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    cell = next(cell for cell in manifest["workloads"]
                if cell["name"] == "rewrite-batch")
    assert cell["config"] == CONFIG["name"] and cell["chips"] == 1
    loaded = run.load_cell("rewrite-batch")
    workload = loaded["workload"]
    assert (workload["loop"], workload["sessions"]) == ("closed", 48)
    assert workload["payload"]["text"]["text_tokens"] == {
        "dist": "loguniform", "lo": 64, "hi": 1024, "pool": 256}
    assert workload["new_tokens"] == 128 and workload["window"] == 4
    assert {metric["name"] for metric in loaded["end_to_end"]} == {
        "latency_p50_ms", "tokens_per_s", "setup_s"}
    for name, kind in (
            ("diffusion.tokens_per_row_pass", "histogram_quantile"),
            ("diffusion.commit_pass_share", "histogram_quantile"),
            ("moe.block_experts_touched", "histogram_quantile"),
            ("kernel.block_attention_roofline", "op_roofline")):
        metric = run.layer_metric(name)
        entry = next(entry for entry in manifest["per_layer"]
                     if entry["name"] == name)
        assert metric["kind"] == kind
        assert entry["workloads"] == metric["workloads"] \
            == ["rewrite-batch"]
        assert all(entry[key] == metric[key] for key in
                   ("unit", "better", "source", "layer", "moves"))
        assert entry["moves"] == "tokens_per_s"
        if kind == "op_roofline":
            assert callable(getattr(FAMILY, metric["args"]["count"]))


def test_element_parameters_are_the_family_and_its_widths():
    handed = FAMILY.element_parameters(CONFIG)
    assert handed["family"] == "sdar_moe"
    assert set(handed["widths"]) == set(FAMILY.WIDTH_FIELDS)
    from aiko_services_tpu.models.families import (FAMILY_WIDTHS,
                                                   family_spec_error)
    assert FAMILY.WIDTH_FIELDS == FAMILY_WIDTHS["sdar_moe"]
    served = CONFIG["definition"]["elements"][0]["parameters"]
    assert family_spec_error({**served, **handed}) is None


def test_width_differences_against_a_served_config():
    from aiko_services_tpu.models.sdar import SdarConfig
    served = SdarConfig.from_widths(
        FAMILY.element_parameters(CONFIG)["widths"], max_seq=1280,
        denoising_steps=2)
    batcher = types.SimpleNamespace(config=served)
    assert FAMILY.width_differences(CONFIG, batcher) == []
    assert FAMILY.published_widths(served)["num_experts"] == 128
    other = types.SimpleNamespace(config=dataclasses.replace(
        served, n_layers=6, max_seq=2048, denoising_steps=4,
        mask_token=7))
    assert {key for key, _, _ in FAMILY.width_differences(
        CONFIG, other)} == {"num_hidden_layers", "max_position_embeddings",
                            "denoising_steps", "mask_token"}
    assert ("norm_topk_prob", False, True) in FAMILY.width_differences(
        {**CONFIG, "norm_topk_prob": False}, batcher)


# ISSUE 36's arithmetic, by hand.
ATTENTION = 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128    # 18,874,368
ROUTER = 2048 * 128
EXPERT = 3 * 2048 * 768                                 # 4,718,592
HEAD = 2048 * 151936
KV_TOKEN = 8 * 2 * 4 * 128 * 2                          # 16,384 B


def test_the_pieces_of_the_count():
    assert FAMILY.attention_weights(CONFIG) == ATTENTION == 18_874_368
    assert FAMILY.expert_weights(CONFIG) == EXPERT == 4_718_592
    layer = ATTENTION + ROUTER + 128 * EXPERT + 2 * 2048 + 2 * 128
    assert round(layer / 1e6, 1) == 623.1               # 1.246 GB
    assert round(2 * (8 * layer + 2 * HEAD + 2048) / 1e9, 2) == 11.21
    assert FAMILY.cache_bytes_per_token(CONFIG) == KV_TOKEN == 16_384
    assert round(321 * 128 * KV_TOKEN / 1e9, 2) == 0.67
    # 37.33 tokens a pass are 28 live rows of 4 positions
    assert FAMILY.live_rows(CONFIG, 112 / 3) == (pytest.approx(28.0), 4)
    assert FAMILY.experts_touched(CONFIG, 112) == pytest.approx(
        128 * (1 - (120 / 128) ** 112))
    assert 127.8 < FAMILY.experts_touched(CONFIG, 112) < 128
    assert FAMILY.experts_touched(CONFIG, 4) == pytest.approx(
        128 * (1 - (120 / 128) ** 4))
    assert FAMILY.quota(4, 2, 0) == FAMILY.quota(4, 2, 1) == 2
    assert [FAMILY.quota(4, 3, done) for done in range(3)] == [2, 1, 1]


@pytest.mark.parametrize("tokens,context", [(4 / 3, 0), (112 / 3, 600),
                                            (128 / 3, 1100)])
def test_a_pass_to_the_byte(tokens, context):
    live = tokens * 3 / 4
    positions = live * 4
    touched = 128 * (1 - (120 / 128) ** positions)
    work = FAMILY.decode_step(CONFIG, tokens, context)
    streamed = 8 * (ATTENTION + ROUTER + touched * EXPERT) + HEAD
    multiplied = 8 * (ATTENTION + ROUTER + 8 * EXPERT) + HEAD
    attention = live * context * 8 * 4 * 32 * 4.0 * 128
    assert work["bytes"] == pytest.approx(
        2 * streamed + live * context * KV_TOKEN, rel=1e-12)
    assert work["operations"] == pytest.approx(
        2.0 * multiplied * positions + attention, rel=1e-12)
    kernel = FAMILY.block_decode_attention(CONFIG, tokens, context)
    assert kernel["bytes"] == pytest.approx(live * context * KV_TOKEN)
    assert kernel["operations"] == pytest.approx(attention)


def test_a_full_pass_streams_what_the_issue_reckoned():
    # 9.7 GB of experts, 0.3 GB of attention projections, 0.6 GB of head
    # and ~0.3 GB of K/V rows for ~37 tokens
    work = FAMILY.decode_step(CONFIG, 112 / 3, 600)
    cache = 28 * 600 * KV_TOKEN
    assert 0.27e9 < cache < 0.28e9
    assert 10.5e9 < work["bytes"] - cache < 10.7e9
    assert 9.6e9 < 2 * 8 * 128 * EXPERT < 9.7e9


def _context(ops, slice_counters):
    """A reader's context over a made-up cut: ``ops`` are (name,
    start_ns, duration_ns) of one device's op line."""
    return types.SimpleNamespace(
        architecture=FAMILY, config=CONFIG, notes={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        cut={"devices": {"/device:TPU:0": {"modules": [], "ops": ops}},
             "host": []},
        slice_counters=slice_counters,
        counters={"client.answered": 100,
                  "batcher.prefill_tokens": 34_600},
        workload={"new_tokens": 128},
        metric=lambda name: {"batcher.rows_per_step": 112 / 3}[name])


def _args(name):
    return json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", f"{name}.json")))["args"]


def test_the_kernels_share_from_a_cut():
    # 48 calls (6 passes x 8 layers) of 0.1 ms inside a while of 100 ms
    ops = [["%while.1 = while(...)", 0, 100_000_000]] + [
        [f"%flash_decode_attention_paged.{index % 8} = custom-call(...)",
         1000 + index * 200_000, 100_000] for index in range(48)]
    context = _context(ops, {"batcher.steps": 6})
    share = op_roofline.read(_args("kernel.block_attention_roofline"),
                             context)
    least = 28 * (346 + 64) * KV_TOKEN / 819e9          # memory-bound
    assert share == pytest.approx(100 * least * 6 / (48 * 0.1e-3))
    assert 0 < share < 100
    assert context.notes["block_decode_attention"]["bound"] == "memory"
    # the parent's programs have no such op here: nothing, not an error
    assert op_roofline.read(
        _args("kernel.block_attention_roofline"),
        _context([["%fusion.1 = fusion(...)", 0, 1000]],
                 {"batcher.steps": 6})) is None


def test_the_reference_checks_controls_fail_at_a_small_size():
    """bfloat16 served, tiny widths: the check passes pass by pass,
    denoising and commit alike; the reference in fp8 -- the nearest
    precision below the stated one -- and the reference with a causal
    mask inside the block both fail the same limit by far."""
    import jax
    from aiko_services_tpu.models import sdar
    from aiko_services_tpu.models.batching import ContinuousBatcher
    config = dataclasses.replace(sdar.SdarConfig.tiny(),
                                 denoising_steps=2)
    params = sdar.init_params(jax.random.PRNGKey(3), config)
    batcher = ContinuousBatcher(params, config, max_slots=3, max_seq=256,
                                prefill_chunk=64, kv_page_tokens=16,
                                decode_block_tokens=8)
    spec = {"prompt_tokens": 70, "short_prompt_tokens": 6, "blocks": 3,
            "loop_prompt_tokens": [5, 18, 39], "loop_join": 2,
            "tolerance": 0.2}
    served = FAMILY.check_reference(batcher, 7, spec)
    assert served["max_abs_diff"] < 0.2, served
    # three blocks past each prompt, two denoising passes and a commit
    assert served["passes"] >= 2 * 3 * 2
    assert served["positions"] == 4 * served["passes"]
    assert served["router_not_near_ties"] == 0
    # ... and the device loop on three slots at once: what each stored
    # reads back inside the limit, what each decided the rule explains
    assert served["loop_blocks"] == 3 * 3 and not served["loop_mismatches"]
    assert served["loop_readback_max_abs_diff"] < 0.2
    assert served["loop_commits"] >= served["loop_blocks"]
    for control in ("fp8_activations", "causal_in_block"):
        failed = FAMILY.check_reference(batcher, 7, spec, control)
        assert failed["max_abs_diff"] > 2 * 0.2, (control, failed)
        assert failed["loop_readback_max_abs_diff"] > 2 * 0.2
    with pytest.raises(ValueError, match="control"):
        FAMILY.check_reference(batcher, 7, spec, "int4")
    assert batcher._pages.free_pages == batcher._pages.total - 1


def test_the_cell_walks_on_the_cpu():
    """``--rehearse --workload rewrite-batch``: the whole harness path
    at the family's tiny widths, the reference check inside its limit,
    every check true, the cell's own metrics walked."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--rehearse",
         "--workload", "rewrite-batch", "--seed", "3600000007",
         "--seconds", "2", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads([line for line in done.stdout.splitlines()
                       if line.startswith('{"rehearsal"')][-1])
    assert line["correct"] is True and line["failed"] == 0
    assert {"diffusion.tokens_per_row_pass", "diffusion.commit_pass_share",
            "moe.block_experts_touched"} <= set(line["metrics_walked"])
