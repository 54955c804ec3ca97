"""The cohere2_moe architecture file: its counts by hand at Command A+'s
published widths and the cell's cut, the configuration file against the
catalog row it was copied from, the manifest's new files, the two new
roofline shares on a made-up cut, the bounds of the two page pools, the
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
                       "text-command-a-plus-05-2026.json")
FAMILY = architectures.load(CONFIG, [run.HERE])
SERVED = CONFIG["definition"]["elements"][0]["parameters"]

# The catalog row's ``config`` (model-configs guide,
# command-a-plus-05-2026), but for its 32 layer types.
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "logit_scale": 1, "max_position_embeddings": 200000,
    "model_type": "cohere2_moe", "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "order_of_interleaved_layers": "local_attn_first",
    "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
    "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
    "rope_theta": 50000, "rotary_pct": 1,
    "shared_expert_combination_strategy": "average", "sliding_window": 4096,
    "tf_legacy_loss": False, "tie_word_embeddings": True,
    "use_embedding_sharing": True, "use_gated_activation": True,
    "use_parallel_block": True, "use_parallel_embedding": False,
    "use_qk_norm": False, "vocab_size": 262144}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


def test_the_file_holds_every_published_key_to_the_letter():
    differ = {key for key, value in PUBLISHED.items()
              if CONFIG.get(key, "absent") != value}
    assert differ | {"layer_types"} == set(CONFIG["reduced"])
    assert CONFIG["layer_types"] == PERIOD
    assert CONFIG["source_values"]["layer_types"] == PERIOD * 8
    assert {key: CONFIG["source_values"][key] for key in differ} \
        == {key: PUBLISHED[key] for key in differ}
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 16, 32768)
    assert CONFIG["expert_share"] == {"shards": 8, "shard": 0}
    # the floors of a model_config cut: a whole period, >= 4 layers,
    # >= 8 routed experts, >= an eighth of the vocabulary
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert {"shared_expert_combination", "rotary_by_layer", "weights",
            "serving_precision", "tokenizer", "ignore_eos",
            "garbage_collection"} <= set(CONFIG["assumed"])
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = next(entry for entry in manifest["configs"]
                 if entry["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"] \
        == "https://huggingface.co/CohereLabs/command-a-plus-05-2026/" \
           "blob/main/config.json"
    assert SERVED["max_seq"] == CONFIG["max_position_embeddings"] == 8448
    assert "quantize" not in SERVED and SERVED["attention"] == "flash"
    checked = CONFIG["reference"]
    assert checked["prompt_tokens"] > PUBLISHED["sliding_window"] + 512
    # the loop stretch decodes as many rows at once as the cell does
    # (~40 of 48 slots), the few long prompts crossing the window
    assert 40 <= len(checked["loop_prompt_tokens"]) <= SERVED["max_slots"]
    assert sum(length > PUBLISHED["sliding_window"]
               for length in checked["loop_prompt_tokens"]) >= 3


def test_the_manifests_new_files_load():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    cell = next(cell for cell in manifest["workloads"]
                if cell["name"] == "transcript-batch")
    assert cell["config"] == CONFIG["name"] and cell["chips"] == 1
    loaded = run.load_cell("transcript-batch")
    workload = loaded["workload"]
    assert (workload["loop"], workload["sessions"]) == ("closed", 64)
    assert workload["payload"]["text"]["text_tokens"] == {
        "dist": "uniform", "lo": 4096, "hi": 8192, "pool": 256}
    assert workload["new_tokens"] == 256 and workload["window"] == 4
    assert {metric["name"] for metric in loaded["end_to_end"]} == {
        "latency_p50_ms", "tokens_per_s", "setup_s"}
    for name, kind in (
            ("kernel.window_decode_roofline", "op_roofline"),
            ("kernel.window_prefill_roofline", "op_roofline"),
            ("cache.window_pages_held", "histogram_quantile"),
            ("moe.held_experts_touched", "histogram_quantile")):
        metric = run.layer_metric(name)
        entry = next(entry for entry in manifest["per_layer"]
                     if entry["name"] == name)
        assert metric["kind"] == kind
        assert entry["workloads"] == metric["workloads"] \
            == ["transcript-batch"]
        assert all(entry[key] == metric[key] for key in
                   ("unit", "better", "source", "layer", "moves"))
        if kind == "op_roofline":
            assert callable(getattr(FAMILY, metric["args"]["count"]))
    # every accepted metric without a list is reported here too
    listless = {metric["name"] for metric in manifest["per_layer"]
                if "workloads" not in metric}
    assert listless <= {metric["name"] for metric in loaded["per_layer"]}


def test_element_parameters_are_the_family_its_widths_and_share():
    handed = FAMILY.element_parameters(CONFIG)
    assert handed["family"] == "cohere2_moe"
    assert handed["expert_shards"] == 8
    from aiko_services_tpu.models.families import (FAMILY_WIDTHS,
                                                   family_spec_error)
    assert FAMILY.WIDTH_FIELDS == FAMILY_WIDTHS["cohere2_moe"]
    assert family_spec_error({**SERVED, **handed}) is None


def test_width_differences_against_a_served_config():
    from aiko_services_tpu.models.command_a import CommandAConfig
    handed = FAMILY.element_parameters(CONFIG)
    served = CommandAConfig.from_widths(handed["widths"], max_seq=8448,
                                        expert_shards=8)
    assert served.router_experts == 128 and served.held == (0, 16)
    batcher = types.SimpleNamespace(config=served)
    assert FAMILY.width_differences(CONFIG, batcher) == []
    shard = types.SimpleNamespace(config=dataclasses.replace(
        served, expert_shards=4))
    assert [key for key, *_ in FAMILY.width_differences(CONFIG, shard)] \
        == ["expert_share"]
    assert FAMILY.width_differences(
        {**CONFIG, "use_parallel_block": False}, batcher)


# The cell's arithmetic, by hand.
ATTENTION = 2 * 4096 * 128 * 128 + 2 * 4096 * 8 * 128    # 142,606,336
ROUTER = 4096 * 128                                      # 524,288
EXPERT = 3 * 4096 * 4096                                 # 50,331,648
SHARED = 4 * EXPERT                                      # 201,326,592
HEAD = 4096 * 32768
KV = 2 * 8 * 128 * 2                                     # 4,096 B


def test_the_pieces_of_the_count():
    assert FAMILY.attention_weights(CONFIG) == ATTENTION == 142_606_336
    assert FAMILY.expert_weights(CONFIG) == EXPERT == 50_331_648
    assert FAMILY.router_experts(CONFIG) == 128
    assert FAMILY.kv_bytes_per_token(CONFIG) == KV == 4096
    outside = ATTENTION + ROUTER + SHARED
    assert round(outside / 1e6, 2) == 344.46
    held = outside + 16 * EXPERT + 4096                  # the norm
    assert round(held / 1e6, 1) == 1149.8
    assert round(2 * (4 * held + HEAD + 4096) / 1e9, 3) == 9.467
    # the published model, for the row's 218B-A25B
    whole = 32 * (outside + 128 * EXPERT) + 262144 * 4096
    active = 32 * (outside + 8 * EXPERT) + 262144 * 4096
    assert round(whole / 1e9, 1) == 218.3 and round(active / 1e9) == 25
    assert FAMILY.held_experts_touched(CONFIG, 40) == pytest.approx(
        16 * (1 - (15 / 16) ** 40))
    assert 14.7 < FAMILY.held_experts_touched(CONFIG, 40) < 14.9


def test_the_bounds_of_the_two_pools():
    """The global layer holds every page of every slot; a window layer
    at most the window and an admission chunk, straddling a page -- and
    the batcher sizes the window pools so."""
    page, slots, chunk = 128, SERVED["max_slots"], 512
    pages = SERVED["max_seq"] // page
    assert SERVED["kv_pages"] == slots * pages + 1 == 3169
    bound = -(-(CONFIG["sliding_window"] + chunk) // page) + 1
    assert bound == 37 and slots * bound + 1 == 1777
    page_bytes = page * KV
    assert page_bytes == 524_288
    assert round(3169 * page_bytes / 1e9, 3) == 1.661
    assert round(3 * 1777 * page_bytes / 1e9, 3) == 2.795
    weights = 9.467e9
    assert round((weights + 3169 * page_bytes + 3 * 1777 * page_bytes)
                 / 1e9, 2) == 13.92
    # window layers that kept every page would not fit a 16 GB chip
    assert round(weights / 1e9 + 4 * 3169 * page_bytes / 1e9, 2) == 16.11
    from aiko_services_tpu.models.batching import ContinuousBatcher
    from aiko_services_tpu.models.command_a import CommandAConfig
    import jax
    config = dataclasses.replace(CommandAConfig.tiny(), window=64)
    from aiko_services_tpu.models import command_a
    params = command_a.init_params(jax.random.PRNGKey(0), config)
    batcher = ContinuousBatcher(params, config, max_slots=3, max_seq=256,
                                prefill_chunk=32, kv_page_tokens=16,
                                decode_block_tokens=8)
    assert batcher.window_pages_per_slot == -(-(64 + 32) // 16) + 1 == 7
    assert batcher.cache["window_k"].shape[:2] == (3, 3 * 7 + 1)
    assert batcher.cache["k"].shape[:2] == (1, 3 * 16 + 1)


@pytest.mark.parametrize("rows,context", [(1, 200), (40, 6272),
                                          (48, 8000)])
def test_a_step_to_the_byte(rows, context):
    touched = min(16 * (1 - (15 / 16) ** rows),
                  CONFIG["measured_routing"]["held_experts_touched"])
    read = rows * (context + 3 * min(context, 4096))
    work = FAMILY.decode_step(CONFIG, rows, context)
    streamed = 4 * (ATTENTION + ROUTER + SHARED + touched * EXPERT) + HEAD
    multiplied = 4 * (ATTENTION + ROUTER + SHARED + 1 * EXPERT) + HEAD
    attention = read * 128 * 4.0 * 128
    assert work["bytes"] == pytest.approx(2 * streamed + read * KV,
                                          rel=1e-12)
    assert work["operations"] == pytest.approx(
        2.0 * multiplied * rows + attention, rel=1e-12)
    kernel = FAMILY.window_decode_attention(CONFIG, rows, context)
    assert kernel["bytes"] == pytest.approx(read * KV)
    assert kernel["operations"] == pytest.approx(attention)


def test_a_step_streams_what_the_cut_reckoned():
    # ~9.0 GB of weights and ~3.0 GB of K/V for ~40 rows at ~6,272 where
    # rows route apart; ~7.3 GB of weights at the held experts a step
    # touched on the chip, which is what the count takes
    cache = 40 * (6272 + 3 * 4096) * KV
    assert 3.0e9 < cache < 3.1e9
    apart = dict(CONFIG, measured_routing={})
    assert 8.9e9 < FAMILY.decode_step(apart, 40, 6272)["bytes"] - cache \
        < 9.1e9
    assert FAMILY.streamed_held_experts(CONFIG, 40) == 10.62
    assert FAMILY.streamed_held_experts(CONFIG, 8) == pytest.approx(
        FAMILY.held_experts_touched(CONFIG, 8))
    assert 7.2e9 < FAMILY.decode_step(CONFIG, 40, 6272)["bytes"] - cache \
        < 7.4e9
    # window layers that read whole contexts would read 4.1 GB
    assert 4.0e9 < 40 * 4 * 6272 * KV < 4.2e9


@pytest.mark.parametrize("prompt", [3000.0, 6272.0])
def test_a_prompt_token_to_the_byte(prompt):
    window = 4096.0
    if prompt <= window:
        banded = (prompt + 1) / 2
    else:
        banded = (window * (window + 1) / 2
                  + (prompt - window) * window) / prompt
    keys = (prompt + 1) / 2 + 3 * banded
    work = FAMILY.window_prefill_attention(CONFIG, 40, prompt)
    assert work["operations"] == pytest.approx(keys * 128 * 4.0 * 128)
    assert work["bytes"] == pytest.approx(
        4 * 2 * 128 * 128 * 2 + keys * KV / 512)


def _context(ops, slice_counters, rows=40.0):
    """A reader's context over a made-up cut: ``ops`` are (name,
    start_ns, duration_ns) of one device's op line."""
    return types.SimpleNamespace(
        architecture=FAMILY, config=CONFIG, notes={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        cut={"devices": {"/device:TPU:0": {"modules": [], "ops": ops}},
             "host": []},
        slice_counters=slice_counters,
        counters={"client.answered": 100,
                  "batcher.prefill_tokens": 614_400},
        workload={"new_tokens": 256},
        metric=lambda name: {"batcher.rows_per_step": rows}[name])


def _args(name):
    return json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", f"{name}.json")))["args"]


def test_the_kernels_shares_from_a_cut():
    # 24 decode calls (6 steps x 4 layers) of 1 ms; 8 flash calls of 2 ms
    ops = [["%while.1 = while(...)", 0, 100_000_000]] + [
        [f"%flash_decode_attention_paged.{index % 4} = custom-call(...)",
         1000 + index * 2_000_000, 1_000_000] for index in range(24)] + [
        [f"%flash_attention.{index % 4} = custom-call(...)",
         60_000_000 + index * 3_000_000, 2_000_000] for index in range(8)]
    context = _context(ops, {"batcher.steps": 6,
                             "batcher.prefill_tokens": 1024})
    share = op_roofline.read(_args("kernel.window_decode_roofline"),
                             context)
    mean = 6144 + 128
    least = 40 * (mean + 3 * 4096) * KV / 819e9          # memory-bound
    assert share == pytest.approx(100 * least * 6 / (24 * 1e-3))
    assert 0 < share < 100
    assert context.notes["window_decode_attention"]["bound"] == "memory"
    prefill = op_roofline.read(_args("kernel.window_prefill_roofline"),
                               context)
    work = FAMILY.window_prefill_attention(CONFIG, 40, mean)
    assert prefill == pytest.approx(
        100 * work["operations"] / 197e12 * 1024 / (8 * 2e-3))
    assert context.notes["window_prefill_attention"]["bound"] == "compute"
    # the parent's programs have no such op here: nothing, not an error
    assert op_roofline.read(
        _args("kernel.window_decode_roofline"),
        _context([["%fusion.1 = fusion(...)", 0, 1000]],
                 {"batcher.steps": 6})) is None


def test_the_reference_checks_controls_fail_at_a_small_size():
    """bfloat16 served, tiny widths, a window of 16 over pages of 8:
    the check passes, one sequence and the device loop alike; the
    reference in fp8 -- the nearest precision below the stated one --
    and the reference whose window layers attend the whole causal past
    both fail the same limit by far."""
    import jax
    from aiko_services_tpu.models import command_a
    from aiko_services_tpu.models.batching import ContinuousBatcher
    config = dataclasses.replace(command_a.CommandAConfig.tiny(),
                                 n_experts=4, expert_shards=2)
    params = command_a.init_params(jax.random.PRNGKey(3), config)
    batcher = ContinuousBatcher(params, config, max_slots=8, max_seq=128,
                                prefill_chunk=16, kv_page_tokens=8,
                                decode_block_tokens=8)
    spec = {"prompt_tokens": 41, "decode_steps": 10,
            "loop_prompt_tokens": [5, 40, 23, 31, 17, 44, 12, 28],
            "loop_new_tokens": 8, "loop_join": 2}
    served = FAMILY.check_reference(batcher, 7, spec)
    limit = 0.2 * served["logit_std"]
    assert served["max_abs_diff"] < limit, served
    assert served["router_not_near_ties"] == 0
    assert not served["loop_mismatches"]
    assert served["window_pages_released"] > 0
    for control in ("fp8_activations", "causal_windows"):
        failed = FAMILY.check_reference(batcher, 7, spec, control)
        assert failed["max_abs_diff"] > limit, (control, failed)
    assert batcher._pages.free_pages == batcher._pages.total - 1


def test_the_cell_walks_on_the_cpu():
    """``--rehearse --workload transcript-batch``: the whole harness
    path at the family's tiny widths (the real window, prompts and
    reference sizes), the reference check inside its limit, every check
    true, the cell's own metrics walked."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--rehearse",
         "--workload", "transcript-batch", "--seed", "3600000007",
         "--seconds", "2", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=2400,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads([line for line in done.stdout.splitlines()
                       if line.startswith('{"rehearsal"')][-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["reference_max_abs_diff"][0] \
        < line["compared"]["reference_max_abs_diff"][1]
