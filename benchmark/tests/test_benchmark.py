"""The benchmark's own arithmetic and wiring (``python -m pytest
benchmark/tests -q``): nothing here needs a device."""

import gzip
import importlib
import json
import os

import pytest

from benchmark import architectures, readers, roofline, run, trace, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as stream:
        return json.load(stream)


MANIFEST = load("BENCHMARK.json")


# -- percentiles and sample counts ------------------------------------------

@pytest.mark.parametrize("values,q,want", [
    ([10.0], 95, 10.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    (list(range(1, 101)), 95, 95.05),
    ([5.0, 1.0, 3.0], 0, 1.0),
    ([5.0, 1.0, 3.0], 100, 5.0),
])
def test_percentile_interpolates(values, q, want):
    assert traffic.percentile(values, q) == pytest.approx(want)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        traffic.percentile([], 50)


@pytest.mark.parametrize("count,q,want", [(192, 95, 9), (200, 95, 10),
                                          (400, 95, 20), (19, 95, 0)])
def test_samples_beyond_a_percentile(count, q, want):
    assert traffic.samples_beyond(count, q) == want


def test_window_numbers_count_by_due_time_and_arrival():
    records = [
        {"due_s": 0.5, "sent_s": 0.5, "recv_s": 1.2, "status": "ok"},
        {"due_s": 1.0, "sent_s": 1.001, "recv_s": 1.5, "status": "ok"},
        {"due_s": 1.9, "sent_s": 1.9, "recv_s": 2.4, "status": "ok"},
        {"due_s": 1.5, "sent_s": 1.5, "recv_s": None,
         "status": "unanswered"},
        {"due_s": 2.0, "sent_s": 2.0, "recv_s": 2.1, "status": "ok"},
    ]
    numbers = traffic.window_numbers(records, 1.0, 1.0, new_tokens=24)
    assert numbers["attempted"] == 3 and numbers["failed"] == 1
    assert numbers["latencies_ms"] == pytest.approx([500.0, 500.0])
    # answered inside [1, 2): the first (due before the window) and
    # the second; the third arrived after it closed.
    assert numbers["tokens_per_s"] == 48.0
    assert numbers["generator_late_ms"]["max"] == pytest.approx(1.0)


# -- the schedule is a function of the seed -----------------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_schedule_is_a_function_of_the_seed(cell):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    spec = load("benchmark", "workloads", f"{entry['traffic']}.json")
    one, again, other = (traffic.Payloads(spec, seed)
                         for seed in (3000000011, 3000000011, 5))
    for session in range(3):
        for index in range(4):
            assert one.make(session, index) == again.make(session, index)
    if spec["loop"] == "open":
        assert traffic.phase_offsets(spec, 7) \
            == traffic.phase_offsets(spec, 7)
        # Every seed: the same set of offsets, dealt out differently.
        assert sorted(traffic.phase_offsets(spec, 7)) \
            == sorted(traffic.phase_offsets(spec, 8))
    for field in one.orders:
        assert sorted(one.orders[field]) == sorted(other.orders[field])
        assert one.orders[field] != other.orders[field]


def test_prompt_text_has_the_token_count_asked_for():
    spec = {"sessions": 2, "payload": {"text": {"text_tokens": {
        "dist": "loguniform", "lo": 128, "hi": 1536, "pool": 64}}}}
    payloads = traffic.Payloads(spec, 1)
    pool = traffic.length_pool(spec["payload"]["text"]["text_tokens"])
    assert min(pool) >= 128 and max(pool) <= 1536
    text = payloads.make(1, 3)["text"]
    # ByteTokenizer: one token a byte, plus BOS.
    assert len(text.encode()) + 1 == payloads.tokens("text", 1, 3)
    assert text != payloads.make(0, 3)["text"]


def test_in_order_wants_every_request_answered_once_in_turn():
    good = [{"session": 0, "frame": f, "answers": 1, "position": f + 4}
            for f in range(3)]
    assert traffic.in_order(good)
    assert not traffic.in_order(good + [
        {"session": 1, "frame": 0, "answers": 0}])
    swapped = [dict(r) for r in good]
    swapped[1]["position"], swapped[2]["position"] = 6, 5
    assert not traffic.in_order(swapped)


# -- every file the manifest names exists and loads ---------------------------

def test_manifest_names_files_that_load():
    names = {c["name"] for c in MANIFEST["configs"]}
    for config in MANIFEST["configs"]:
        assert config["file"].startswith(tuple(MANIFEST["paths"]))
        data = load(config["file"])
        assert data["source"] == config["source"]
        assert data["reduced"] == config["reduced"]
        for key in ("hidden_size", "num_hidden_layers", "vocab_size",
                    "intermediate_size", "assumed", "definition"):
            assert key in data
    for cell in MANIFEST["workloads"]:
        assert cell["config"] in names
        spec = load("benchmark", "workloads", f"{cell['traffic']}.json")
        assert spec["loop"] in ("open", "closed") and spec["limits_s"]
    end_to_end = {m["name"] for m in MANIFEST["end_to_end"]}
    for metric in MANIFEST["per_layer"]:
        spec = load("benchmark", "layer_metrics", f"{metric['name']}.json")
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == metric[key]
        assert spec.get("workloads") == metric.get("workloads")
        assert metric["moves"] in end_to_end
        assert callable(readers.load(spec["kind"]).read)


def test_a_reader_kind_is_found_by_file_name():
    for path in os.listdir(os.path.join(ROOT, "benchmark", "readers")):
        if path.endswith(".py") and path != "__init__.py":
            module = readers.load(path[:-3])
            assert module is importlib.import_module(
                f"benchmark.readers.{path[:-3]}")
    with pytest.raises(ImportError):
        readers.load("no_such_kind")


# -- the yardstick ---------------------------------------------------------------

def test_decode_step_bytes_of_the_published_widths():
    widths = load("benchmark", "configs", "caption-internlm2-1.8b.json")
    family = architectures.load(widths, run.data_directories())
    assert family.__name__ == "benchmark.architectures.llama"
    assert family.layer_matmul_weights(widths) == 62_914_560
    assert family.matmul_weights(widths) == 1_699_479_552
    assert family.cache_bytes_per_token(widths) == 98_304
    work = family.decode_step(widths, rows=16, context_tokens=100)
    assert work["bytes"] == 1_699_479_552 + 16 * 100 * 98_304
    least_s, bound = roofline.least_seconds(
        work, roofline.peaks_for("TPU v5 lite"))
    assert bound == "memory"
    assert least_s == pytest.approx(work["bytes"] / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9")


# -- the trace reduction, against a recorded cut --------------------------------

@pytest.fixture(scope="module")
def cut():
    path = os.path.join(ROOT, "benchmark", "testdata",
                        "camera_paced_cut.json.gz")
    with gzip.open(path, "rt") as stream:
        return json.load(stream)


def test_reduction_of_the_recorded_cut(cut):
    expected = cut["expected"]
    busy_s, window_s = trace.busy_and_window(cut)
    assert busy_s == pytest.approx(expected["busy_s"])
    assert window_s == pytest.approx(expected["window_s"])
    assert 0.0 < busy_s < window_s
    decode = trace.program_durations(cut, "decode_loop")
    prefill = trace.program_durations(cut, "prefill_into_slot")
    assert len(decode) == expected["decode_programs"]
    assert len(prefill) == expected["prefill_programs"]
    assert sum(decode) == pytest.approx(expected["decode_s"])
    # (a program cut off by the slice's end counts here, not above)
    summary = trace.programs_ms(cut)
    assert len(decode) <= summary["jit__decode_loop_jit"]["runs"] \
        <= len(decode) + 1
    assert list(summary) == sorted(
        summary, key=lambda name: -summary[name]["sum"])
    ops = trace.top_device_ops(cut)
    assert [name for name, _ in ops[:3]] == expected["top_ops"]
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1]
    # Self times never add up to more than the device was busy.
    assert sum(seconds for _, seconds in trace.top_device_ops(
        cut, 10_000)) <= busy_s * 1.0001
    gaps = trace.idle_gaps(cut)
    assert [name for name, _ in gaps[:3]] == expected["top_gaps"]
    assert sum(seconds for _, seconds in trace.idle_gaps(cut, 10_000)) \
        == pytest.approx(window_s - busy_s)


def test_self_time_subtracts_nested_ops():
    events = [["%while.1 = x", 0, 100], ["%fusion.2 = y", 10, 30],
              ["%fusion.2 = y", 50, 30], ["%copy.3 = z", 200, 5]]
    assert trace.self_times(events) == {"while.1": 40, "fusion.2": 60,
                                        "copy.3": 5}
    assert trace.short_name("%fusion.265 = bf16[16]{0} fusion(...)") \
        == "fusion.265"
    assert trace.program_name("jit__decode_loop_jit(9594)") \
        == "jit__decode_loop_jit"


def test_gap_is_named_by_the_host_span_that_covers_most_of_it():
    cut = {"devices": {"/device:TPU:0": {"modules": [], "ops": [
        ["a", 0, 10], ["b", 110, 10], ["c", 130, 10]]}},
        "host": [["element:DET", 5, 90], ["element:LLM", 100, 8]]}
    assert trace.idle_gaps(cut) == [["element:DET", 100 / 1e9],
                                    ["no-host-span", 10 / 1e9]]
    assert trace.busy_and_window(cut) == (30 / 1e9, 140 / 1e9)


# -- every micro-batch bucket is built by the first group ---------------------------

class _Groups:
    """What ``_EveryBucket`` stands on: an element's micro-batch calls."""

    name = "R0"

    def __init__(self):
        self.ran, self.finished = [], []

    def get_parameter(self, name, default=None):
        return {"max_batch": 8}.get(name, default), True

    def batch_run(self, context, key, payloads):
        self.ran.append((key, list(payloads)))
        return len(payloads)

    def batch_finish(self, context, key, entries, result):
        self.finished.append(result)
        for complete, payload in entries:
            complete("okay", {"payload": payload})


def test_the_first_group_of_a_key_runs_every_bucket_and_later_ones_only_themselves():
    from benchmark.elements import _EveryBucket

    class Element(_EveryBucket, _Groups):
        pass

    element = Element()
    assert element.batch_run(None, "720p", ["a", "b", "c"]) == 3
    assert [len(group) for _, group in element.ran] == [1, 2, 4, 8, 3]
    assert all(set(group) == {"a"} for _, group in element.ran[:4])
    assert element.finished == [1, 2, 4, 8]     # the caller finishes its own
    assert element.batch_run(None, "720p", ["d"]) == 1
    assert [len(group) for _, group in element.ran[5:]] == [1]
    element.batch_run(None, "1080p", ["e"])
    assert [len(group) for _, group in element.ran[6:]] == [1, 2, 4, 8, 1]


def test_a_frame_says_which_bucket_it_rode_in():
    from benchmark.elements import _EveryBucket

    class Element(_EveryBucket, _Groups):
        pass

    got = []
    Element().batch_finish(
        None, "720p", [(lambda *a: got.append(a), p) for p in "abc"], 3)
    assert got == [("okay", {"payload": p, "R0_group": 4}) for p in "abc"]


def test_the_same_answer_is_asked_per_bucket():
    """The detector rounds otherwise in a bucket of 4 than in one of 1
    (my chip run, PR 28), so two answers to one frame are a fault only
    where both rode in the same buckets."""
    spec = load("benchmark", "workloads", "camera-paced.json")
    assert spec["same_answer"]["key"] == ["camera", "frame", "R0_group",
                                          "DET_group"]
    assert set(spec["same_answer"]["key"]) <= set(spec["keep"])
    definition = load("benchmark", "configs",
                      "caption-internlm2-1.8b.json")["definition"]
    outputs = {e["name"]: [o["name"] for o in e["output"]]
               for e in definition["elements"]}
    assert "R0_group" in outputs["R0"] and "DET_group" in outputs["DET"]


def _checked(answers):
    """``Bench.checks`` over ok records of one camera and frame, given
    ``(DET_group, detections)`` pairs; its verdict."""
    bench = object.__new__(run.Bench)
    bench.workload = load("benchmark", "workloads", "camera-paced.json")
    bench.reference = {"ok": True}
    bench.rehearse = True
    bench.config = {"llm_element": "LLM", "rehearse": {"LLM": {}}}
    records = [
        {"session": 3, "index": 8 * n, "frame": n, "answers": 1,
         "position": n, "status": "ok",
         "data": {"camera": 3, "frame": 8 * n, "text": "a cat",
                  "R0_group": 1, "DET_group": group,
                  "detections": detections}}
        for n, (group, detections) in enumerate(answers)]
    outcome = {"records": records, "window_builds": [], "round_delta": {
        "batcher.tokens_emitted": 24 * len(records),
        "engine.implicit_transfers": 0, "engine.fused_broken": 0}}
    return bench.checks(outcome)


def test_two_answers_to_one_frame_are_a_fault_only_within_one_bucket():
    assert all(_checked([(1, ["cat"]), (1, ["cat"]), (4, ["dog"])]).values())
    verdict = _checked([(1, ["cat"]), (4, ["dog"]), (4, ["cat"])])
    assert not verdict.pop("same_input_same_answer")
    assert all(verdict.values())
