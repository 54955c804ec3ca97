"""The olmo_hybrid architecture file: its counts to the byte at
Olmo-Hybrid-7B's published widths (ISSUE 33's table), the configuration
file against the catalog row it was copied from, a cut ``layer_types``
seen by ``width_differences``, the manifest's new files, the two new
roofline shares on a made-up cut, the reference check's controls at a
small size, and the cell's walk on the CPU."""

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
                       "text-olmo-hybrid-7b.json")
FAMILY = architectures.load(CONFIG, [run.HERE])

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# The catalog row's ``config`` (model-configs guide, Olmo-Hybrid-7B).
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


def test_the_file_holds_every_published_key_to_the_letter():
    differ = {key for key, value in PUBLISHED.items()
              if CONFIG.get(key, "absent") != value}
    assert differ == {"num_hidden_layers", "layer_types",
                      "max_position_embeddings"} == set(CONFIG["reduced"])
    assert CONFIG["source_values"] == {
        key: PUBLISHED[key] for key in CONFIG["reduced"]}
    assert set(CONFIG["reduced_why"]) == set(CONFIG["reduced"])
    # three whole periods, the published 3:1
    assert CONFIG["num_hidden_layers"] == 12
    assert CONFIG["layer_types"] == PERIOD * 3 == PUBLISHED[
        "layer_types"][:12]
    assert {"norm_placement", "rotary", "init", "serving_precision"} \
        <= set(CONFIG["assumed"])
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    entry = next(entry for entry in manifest["configs"]
                 if entry["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    served = CONFIG["definition"]["elements"][0]["parameters"]
    assert served["max_seq"] == CONFIG["max_position_embeddings"] == 4224
    assert served["kv_pages"] == served["max_slots"] * 33 + 1
    assert "quantize" not in served


def test_the_manifests_new_files_load():
    manifest = run.load_json(ROOT, "BENCHMARK.json")
    cell = next(cell for cell in manifest["workloads"]
                if cell["name"] == "history-batch")
    assert cell["config"] == CONFIG["name"] and cell["chips"] == 1
    loaded = run.load_cell("history-batch")
    workload = loaded["workload"]
    assert (workload["loop"], workload["sessions"]) == ("closed", 48)
    assert workload["payload"]["text"]["text_tokens"] == {
        "dist": "loguniform", "lo": 512, "hi": 4096, "pool": 256}
    assert workload["new_tokens"] == 128 and workload["window"] == 4
    for name, kind in (("kernel.gdn_decode_roofline", "op_roofline"),
                       ("kernel.gdn_prefill_roofline", "op_roofline"),
                       ("cache.state_traffic_share",
                        "histogram_quantile")):
        metric = run.layer_metric(name)
        entry = next(entry for entry in manifest["per_layer"]
                     if entry["name"] == name)
        assert metric["kind"] == kind
        assert entry["workloads"] == metric["workloads"] \
            == ["history-batch"]
        assert all(entry[key] == metric[key] for key in
                   ("unit", "better", "source", "layer", "moves"))
        if kind == "op_roofline":
            assert callable(getattr(FAMILY, metric["args"]["count"]))


def test_element_parameters_are_the_family_and_its_widths():
    handed = FAMILY.element_parameters(CONFIG)
    assert handed["family"] == "olmo_hybrid"
    assert set(handed["widths"]) == set(FAMILY.WIDTH_FIELDS)
    assert handed["widths"]["layer_types"] == PERIOD * 3
    from aiko_services_tpu.models.families import (FAMILY_WIDTHS,
                                                   family_spec_error)
    assert FAMILY.WIDTH_FIELDS == FAMILY_WIDTHS["olmo_hybrid"]
    assert family_spec_error(handed) is None


def test_width_differences_see_a_cut_layer_types():
    from aiko_services_tpu.models.olmo_hybrid import OlmoHybridConfig
    served = OlmoHybridConfig.from_widths(
        FAMILY.element_parameters(CONFIG)["widths"], max_seq=4224)
    batcher = types.SimpleNamespace(config=served)
    assert FAMILY.width_differences(CONFIG, batcher) == []
    assert FAMILY.published_widths(served)["linear_key_head_dim"] == 96
    # two periods served where the file says three: the depth AND the
    # per-layer list differ, and the context
    shallow = types.SimpleNamespace(config=dataclasses.replace(
        served, n_layers=8, layer_types=tuple(PERIOD * 2), max_seq=2048))
    assert {key for key, _, _ in FAMILY.width_differences(
        CONFIG, shallow)} == {"num_hidden_layers", "layer_types",
                              "max_position_embeddings"}
    # the same depth in another pattern: the list alone
    other = types.SimpleNamespace(config=dataclasses.replace(
        served, layer_types=tuple((["linear_attention"] * 5
                                   + ["full_attention"]) * 2)))
    assert [key for key, _, _ in FAMILY.width_differences(
        CONFIG, other)] == ["layer_types"]
    assert ("rope_parameters", {"rope_theta": 10000.0},
            {"rope_theta": None}) in FAMILY.width_differences(
        {**CONFIG, "rope_parameters": {"rope_theta": 10000.0}}, batcher)


# ISSUE 33's table, by hand.
LINEAR_MIXER = 2 * 3840 * 2880 + 2 * 3840 * 5760 + 5760 * 3840 \
    + 3840 * 60                     # + W_a, W_b
FFN = 3 * 3840 * 11008
FULL_MIXER = 4 * 3840 * 3840
HEAD = 3840 * 100352
STATE = 30 * 96 * 192 * 4           # one slot, one layer, float32
TAIL = 3 * 11520 * 2
TOKEN = 30 * (2 * 96 + 2 * 192) * 2 + 30 * 2 * 4
KV_TOKEN = 3 * 2 * 3840 * 2


def test_the_pieces_of_the_count():
    linear, full = FAMILY.layer_weights(CONFIG)
    assert (linear, full) == (LINEAR_MIXER + FFN, FULL_MIXER + FFN)
    # the issue's 215.4 M and 185.8 M (W_a, W_b are 0.23 M of the first)
    assert linear == 215_516_160 and abs(linear / 1e6 - 215.4) < 0.2
    assert round(full / 1e6, 1) == 185.8
    weights = 9 * linear + 3 * full + 2 * HEAD
    assert round(2 * weights / 1e9, 2) == 6.54          # resident, bf16
    assert FAMILY.state_bytes(CONFIG) == STATE == 2_211_840
    assert 9 * STATE == 19_906_560                      # 19.9 MB a slot
    assert FAMILY.tail_bytes(CONFIG) == TAIL
    assert 9 * TAIL == 622_080                          # 0.62 MB a slot
    assert FAMILY.kv_bytes_per_token(CONFIG) == KV_TOKEN == 46_080
    assert round(1057 * 128 * KV_TOKEN / 1e9, 2) == 6.23
    assert FAMILY.token_bytes(CONFIG) == TOKEN
    assert FAMILY.recurrence_operations(CONFIG) == 7 * 30 * 96 * 192
    assert FAMILY.prefill_chunk(CONFIG) == 512


@pytest.mark.parametrize("rows,context", [(1, 0), (27, 1800), (32, 4160)])
def test_a_decode_step_to_the_byte(rows, context):
    work = FAMILY.decode_step(CONFIG, rows, context)
    streamed = 9 * (LINEAR_MIXER + FFN) + 3 * (FULL_MIXER + FFN) + HEAD
    recurrent = rows * 9 * (2 * STATE + 2 * TAIL + TOKEN)
    assert work["bytes"] == pytest.approx(
        2 * streamed + recurrent + rows * context * KV_TOKEN, rel=1e-12)
    assert work["operations"] == pytest.approx(
        2.0 * streamed * rows + rows * 9 * 7 * 30 * 96 * 192
        + rows * context * 3 * 4 * 3840, rel=1e-12)
    kernel = FAMILY.gated_delta_decode(CONFIG, rows, context)
    assert kernel["bytes"] == recurrent
    assert kernel["operations"] == rows * 9 * 7 * 30 * 96 * 192


def test_a_full_step_streams_what_the_issue_reckoned():
    # 5.0 GB of weights (the embedding table is not streamed), ~1.1 GB
    # of state both ways and ~2.2 GB of K/V rows at 27 rows x 1.8 k.
    work = FAMILY.decode_step(CONFIG, 27, 1800)
    cache = 27 * 1800 * KV_TOKEN
    state = FAMILY.gated_delta_decode(CONFIG, 27, 1800)["bytes"]
    assert 2.2e9 < cache < 2.3e9 and 1.0e9 < state < 1.2e9
    assert 5.7e9 < work["bytes"] - cache - state < 5.8e9
    token = FAMILY.gated_delta_chunk(CONFIG)
    assert token["bytes"] == 9 * (TOKEN + 2 * STATE / 512)
    assert token["operations"] == 9 * 7 * 30 * 96 * 192


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
                  "batcher.prefill_tokens": 173_600},
        workload={"new_tokens": 128},
        metric=lambda name: {"batcher.rows_per_step": 27.0}[name])


def _args(name):
    return json.load(open(os.path.join(
        ROOT, "benchmark", "layer_metrics", f"{name}.json")))["args"]


def test_the_decode_kernels_share_from_a_cut():
    # 72 calls (8 steps x 9 layers) of 0.3 ms inside a while of 120 ms
    ops = [["%while.1 = while(...)", 0, 120_000_000]] + [
        [f"%gated_delta_decode_step.{index % 9} = custom-call(...)",
         1000 + index * 400_000, 300_000] for index in range(72)]
    context = _context(ops, {"batcher.steps": 8})
    share = op_roofline.read(_args("kernel.gdn_decode_roofline"), context)
    least = 27 * 9 * (2 * STATE + 2 * TAIL + TOKEN) / 819e9
    assert share == pytest.approx(100 * least * 8 / (72 * 0.3e-3))
    assert 0 < share < 100
    assert context.notes["gated_delta_decode"]["bound"] == "memory"


def test_the_chunk_scans_share_from_a_cut():
    # 27 calls (3 chunks x 9 layers) of 0.5 ms; the slice admitted
    # 1,400 prompt tokens in those three chunks
    ops = [[f"%gated_delta_chunk_scan.{index % 9} = custom-call(...)",
            1000 + index * 600_000, 500_000] for index in range(27)]
    context = _context(ops, {"batcher.prefill_tokens": 1400})
    share = op_roofline.read(_args("kernel.gdn_prefill_roofline"), context)
    least = 9 * (TOKEN + 2 * STATE / 512) / 819e9       # memory-bound
    assert share == pytest.approx(100 * least * 1400 / (27 * 0.5e-3))
    assert 0 < share < 100


def test_the_shares_are_silent_without_the_kernels():
    # the parent's programs have no such op: nothing, not an error
    counters = {"batcher.steps": 8, "batcher.prefill_tokens": 1400}
    for name in ("kernel.gdn_decode_roofline",
                 "kernel.gdn_prefill_roofline"):
        assert op_roofline.read(_args(name), _context(
            [["%fusion.1 = fusion(...)", 0, 1000]], counters)) is None
        untraced = _context([], counters)
        untraced.cut = None
        assert op_roofline.read(_args(name), untraced) is None


def test_the_reference_checks_controls_fail_at_a_small_size():
    """bfloat16 served, tiny widths: the check passes; the reference in
    fp8 -- the nearest precision below the stated one -- fails the same
    limit by far; the state rounded to bfloat16 moves the reading."""
    import jax
    from aiko_services_tpu.models import olmo_hybrid
    from aiko_services_tpu.models.batching import ContinuousBatcher
    config = olmo_hybrid.OlmoHybridConfig.tiny()
    params = olmo_hybrid.init_params(jax.random.PRNGKey(3), config)
    batcher = ContinuousBatcher(params, config, max_slots=3, max_seq=256,
                                prefill_chunk=64, kv_page_tokens=16)
    spec = {"prompt_tokens": 120, "decode_steps": 4}
    served = FAMILY.check_reference(batcher, 7, spec)
    assert served["max_abs_diff"] < 0.25 and served["positions"] == 5
    control = FAMILY.check_reference(batcher, 7, spec, "fp8_activations")
    assert control["max_abs_diff"] > 2 * 0.25
    second = FAMILY.check_reference(batcher, 7, spec, "bf16_state")
    assert second["max_abs_diff"] != served["max_abs_diff"]
    with pytest.raises(ValueError, match="control"):
        FAMILY.check_reference(batcher, 7, spec, "int4")


def test_the_cell_walks_on_the_cpu():
    """``--rehearse --workload history-batch``: the whole harness path
    at the family's tiny widths (same 2:1 pattern in small), the
    reference check inside its limit, every check true."""
    done = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--rehearse",
         "--workload", "history-batch", "--seed", "3300000007",
         "--seconds", "2", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads([line for line in done.stdout.splitlines()
                       if line.startswith('{"rehearsal"')][-1])
    assert line["correct"] is True and line["failed"] == 0
    assert "cache.state_traffic_share" in line["metrics_walked"]
