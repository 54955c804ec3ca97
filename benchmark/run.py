"""One cell of the benchmark, one process:

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are data:
``BENCHMARK.json`` names them and the harness finds
``benchmark/configs/<config>.json`` (through the entry's ``file``),
``benchmark/workloads/<traffic>.json``,
``benchmark/layer_metrics/<metric>.json``,
``benchmark/readers/<kind>.py`` and, by the configuration's
``"architecture"``, ``benchmark/architectures/<name>.py`` by those
names (beside the manifest first, where ``--manifest`` names another).
Nothing here names a cell, a configuration, a metric or a model family.

The run: refuse anything but a TPU (``--rehearse``, never inferred,
walks the same control flow at ``model: tiny`` on whatever backend is
there and marks its line so); build the pipeline from the
configuration's definition through ``create_pipeline`` with the
gateway on; drive it over WebSocket sessions from threads of this
process; warm every shape (first request, reference check, bursts,
rounds of the real traffic until one builds no program); then one
continuous run of the traffic -- ``lead_s`` of it before the window
opens, ``--seconds`` of window, a drain -- and the checks.  Detail
lines first, then the one contract line.

Every wait has a limit (the workload file's ``limits_s``); on expiry
the state of every layer and every thread is dumped and the exit code
is 3, with no result line.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse             # noqa: E402
import faulthandler         # noqa: E402
import gc                   # noqa: E402
import json                 # noqa: E402
import os                   # noqa: E402
import shutil               # noqa: E402
import sys                  # noqa: E402
import tempfile             # noqa: E402
import threading            # noqa: E402
import traceback            # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
MANIFEST = [os.path.join(ROOT, "BENCHMARK.json")]   # ``--manifest``


class Expired(RuntimeError):
    """A wait ran past its limit."""


def load_json(*parts):
    with open(os.path.join(*parts)) as stream:
        return json.load(stream)


def data_directories() -> list:
    """Where a data file is looked for: beside the manifest, then here."""
    beside = os.path.dirname(os.path.abspath(MANIFEST[0]))
    return [HERE] if beside == ROOT else [beside, HERE]


def load_data(*parts):
    found = [directory for directory in data_directories()
             if os.path.isfile(os.path.join(directory, *parts))]
    if not found:
        raise SystemExit(f"benchmark: no {os.path.join(*parts)} in "
                         f"{data_directories()}")
    return load_json(found[0], *parts)


def load_cell(name: str) -> dict:
    """Everything the manifest and the data files say about one cell."""
    manifest = load_json(MANIFEST[0])
    cells = {cell["name"]: cell for cell in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in "
                         f"{MANIFEST[0]} (has: {sorted(cells)})")
    cell = cells[name]
    config_entry = next(entry for entry in manifest["configs"]
                        if entry["name"] == cell["config"])

    def reported(metric):
        return name in metric.get("workloads", [name])

    return {
        "cell": cell,
        "config": load_json(ROOT, config_entry["file"]),
        "workload": load_data("workloads", f"{cell['traffic']}.json"),
        "end_to_end": [m for m in manifest["end_to_end"] if reported(m)],
        "per_layer": [m for m in manifest["per_layer"] if reported(m)],
    }


def layer_metric(name: str) -> dict:
    return load_data("layer_metrics", f"{name}.json")


def merged(base: dict, overrides: dict) -> dict:
    """``base`` with ``overrides`` laid over it, one level into dicts
    (a None removes the key's value)."""
    result = dict(base)
    for key, value in overrides.items():
        if isinstance(value, dict) and isinstance(result.get(key), dict):
            result[key] = {**result[key], **value}
        else:
            result[key] = value
    return result


def detail(**facts):
    print(json.dumps({"note": "detail", **facts}), flush=True)


class Context:
    """What a reader may read (see ``benchmark/readers/__init__.py``)."""

    def __init__(self, bench, numbers, outcome):
        self.config = bench.config
        self.architecture = bench.architecture
        self.workload = bench.workload
        self.peaks = bench.peaks
        self.counters = outcome["counters"]
        self.slice_counters = outcome["slice_counters"]
        self.client_latencies_ms = numbers["latencies_ms"]
        self.requests = numbers["requests"]
        self.frames = outcome["frames"]
        self.cut = outcome["cut"]
        self.window = outcome["window"]
        self.trace_stamp = outcome["trace_stamp"]
        self.host = None        # a reader lays the recorder on the cut
        self.notes: dict = {}
        self._registry = bench.registry
        self._values: dict = {}

    def quantile(self, name, q, labels=None):
        """A series' quantile since the window opened.  Without
        ``labels``: the one series of that name, whatever labels the
        program gave it (tenant and class, where a request had them)."""
        if labels is None:
            found = [series_labels for series, series_labels, _
                     in self._registry.summaries(windowed=False)
                     if series == name]
            if len(found) != 1:
                return None
            labels = found[0]
        return self._registry.quantile(name, q, labels or None,
                                       windowed=False)

    def label_values(self, name, label):
        return sorted({labels[label]
                       for series, labels, _ in self._registry.summaries(
                           windowed=False)
                       if series == name and label in labels})

    def metric(self, name):
        if name not in self._values:
            from benchmark import readers
            spec = layer_metric(name)
            self._values[name] = readers.load(spec["kind"]).read(
                spec.get("args", {}), self)
        return self._values[name]


class Bench:
    """The system under test, built, warmed and ready for windows."""

    def __init__(self, cell_name: str, seed: int, rehearse: bool):
        import jax
        from benchmark import architectures, roofline
        from benchmark.traffic import seed31
        self.loaded = load_cell(cell_name)
        self.rehearse = rehearse
        self.seed = int(seed)
        self.config = self.loaded["config"]
        self.architecture = architectures.load(self.config,
                                               data_directories())
        self.workload = self.loaded["workload"]
        if rehearse:
            self.workload = merged(self.workload,
                                   self.workload.get("rehearse", {}))
        self.limits = self.workload["limits_s"]
        devices = jax.devices()
        self.device = {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}
        self.peaks = None if rehearse \
            else roofline.peaks_for(self.device["kind"])
        self.builds: list = []          # (perf_counter, program name)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_build)
        self._build_pipeline(seed31(seed))
        self.traffic = self.reference = None
        self.warmup_rounds = 0

    # -- construction ------------------------------------------------------

    def _on_build(self, event, duration, **facts):
        if event == BUILD_EVENT:
            self.builds.append((time.perf_counter(),
                                str(facts.get("fun_name"))))

    def builds_since(self, stamp: float) -> list[str]:
        return [name for at, name in self.builds if at >= stamp]

    def _definition(self, seed: int) -> dict:
        config = self.config
        definition = json.loads(json.dumps(config["definition"]))
        overrides = config.get("rehearse", {}) if self.rehearse else {}
        for element in definition["elements"]:
            parameters = element["parameters"]
            if element["name"] == config["llm_element"]:
                parameters.update(
                    self.architecture.element_parameters(config))
            if element["name"] in config.get("seeded", ()):
                parameters["seed"] = seed
            element["parameters"] = {
                key: value for key, value in merged(
                    parameters, overrides.get(element["name"], {})).items()
                if value is not None}
        return definition

    def _build_pipeline(self, seed: int):
        from aiko_services_tpu.pipeline import create_pipeline
        from aiko_services_tpu.runtime import init_process
        self.runtime = init_process(transport="loopback")
        self.runtime.initialize()
        workdir = tempfile.mkdtemp(prefix="benchmark_")
        try:
            path = os.path.join(workdir, "definition.json")
            with open(path, "w") as stream:
                json.dump(self._definition(seed), stream)
            self.pipeline = create_pipeline(path, runtime=self.runtime)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
        self.llm = self.pipeline.graph.get_node(
            self.config["llm_element"]).element
        self.registry = self.pipeline.telemetry.registry

    # -- what the program counts --------------------------------------------

    def counters(self) -> dict:
        batcher = self.llm._batcher
        transfers = self.pipeline.transfer_stats()
        fusion = self.pipeline.fusion_stats()
        counts = {"engine.implicit_transfers": transfers["implicit"],
                  "engine.explicit_transfers": transfers["explicit"],
                  "engine.fused_dispatches": fusion["dispatches"],
                  "engine.fused_broken": fusion["broken"]}
        if batcher is not None:
            for name in ("tokens_emitted", "steps", "prefill_tokens",
                         "blocks_dispatched", "blocks_retired",
                         "evictions", "recoveries"):
                counts[f"batcher.{name}"] = getattr(batcher, name)
        return counts

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {key: value - before.get(key, 0)
                for key, value in after.items()}

    # -- waits with limits ---------------------------------------------------

    def idle(self) -> bool:
        batcher = self.llm._batcher
        return batcher is None or not (
            batcher.active_count or batcher.queue_depth
            or batcher.blocks_in_flight)

    def await_round(self, what: str, limit_s: float, strict=True):
        """Until the round's requests are all replied to and the
        batcher is idle.  ``strict`` (set-up): any reply but ok ends
        the run; the window instead counts them as failed."""
        if not self.traffic.wait(limit_s):
            raise Expired(f"{what}: not answered within {limit_s:.0f} s"
                          f" ({self.traffic.errors[:3]})")
        deadline = time.perf_counter() + 30.0
        while not self.idle():
            if time.perf_counter() > deadline:
                raise Expired(f"{what}: the batcher did not go idle")
            time.sleep(0.01)
        bad = [record for record in self.traffic.records()
               if record["status"] != "ok"] if strict else []
        if bad:
            raise Expired(f"{what}: {len(bad)} request(s) not ok: "
                          f"{bad[:3]}")

    def dump(self, why: str):
        """Where everything stands, for a wait that expired."""
        out = sys.stderr
        print(f"benchmark: {why}", file=out)
        if self.traffic is not None:
            for record in self.traffic.outstanding()[:40]:
                print(f"  outstanding: {record}", file=out)
            print(f"  generator errors: {self.traffic.errors}", file=out)
        gateway = getattr(self.pipeline, "gateway", None)
        if gateway is not None:
            for session in list(gateway.sessions.values()):
                print(f"  gateway session {session.session_id}: "
                      f"inflight={session.inflight} "
                      f"window={session.window} "
                      f"unanswered={sorted(session.unanswered)[:8]}",
                      file=out)
            try:
                print(f"  gateway qos: {self.pipeline.qos_stats()}",
                      file=out)
            except Exception as error:
                print(f"  gateway qos: {error}", file=out)
        batcher = self.llm._batcher
        if batcher is not None:
            print(f"  batcher: active_count={batcher.active_count} "
                  f"queue_depth={batcher.queue_depth} "
                  f"blocks_in_flight={batcher.blocks_in_flight} "
                  f"prefilling={list(batcher._prefilling)} "
                  f"counters={self.counters()}", file=out)
        print(f"  programs built in the last minute: "
              f"{self.builds_since(time.perf_counter() - 60.0)}",
              file=out)
        out.flush()
        faulthandler.dump_traceback(file=out, all_threads=True)

    # -- set-up ---------------------------------------------------------------

    def warm_up(self):
        from benchmark.traffic import Traffic
        workload = self.workload
        self.traffic = Traffic(self.pipeline.gateway.port, workload,
                               self.seed, keep=workload.get("keep", ()))
        self.traffic.open()
        started = time.perf_counter()
        self.traffic.start(0.0, burst=1)        # builds the model
        self.await_round("first request", self.limits["first_request"])
        detail(phase="first request",
               seconds=time.perf_counter() - started,
               programs_built=len(self.builds))
        self.check_reference()
        warmup = workload["warmup"]
        started = time.perf_counter()
        surge = warmup.get("surge")
        if surge:
            # A backlog grows and drains: every micro-batch group size
            # a stall could produce in the window is built here.
            self.traffic.start(float(surge["seconds"]),
                               rate=float(workload["rate"])
                               * float(surge["factor"]))
            self.await_round("warm-up surge", self.limits["round"])
        for size in warmup.get("bursts", ()):
            self.traffic.start(0.0, burst=min(int(size),
                                              len(self.traffic.sessions)))
            self.await_round(f"warm-up burst of {size}",
                             self.limits["burst"])
        for round_index in range(int(warmup["rounds_max"])):
            stamp = time.perf_counter()
            self.traffic.start(float(warmup["round_s"]))
            self.await_round(f"warm-up round {round_index + 1}",
                             self.limits["round"])
            self.warmup_rounds = round_index + 1
            if not self.builds_since(stamp):
                break
        detail(phase="warm-up", seconds=time.perf_counter() - started,
               rounds=self.warmup_rounds,
               programs_built=len(self.builds))

    def check_reference(self):
        """Served against the architecture's plain float32 reference,
        before any window, with nothing else on the device."""
        spec = self.config["reference"]
        started = time.perf_counter()
        with self.llm._device_lock, self.llm._device_scope():
            result = self.architecture.check_reference(
                self.llm._batcher, self.seed, spec)
        result["tolerance"] = float(spec["tolerance"])
        result["ok"] = result["max_abs_diff"] <= result["tolerance"]
        result["seconds"] = time.perf_counter() - started
        self.reference = result
        detail(phase="reference", **result)

    # -- one window --------------------------------------------------------------

    def window(self, seconds: float, trace: bool, keep_cut=None) -> dict:
        """``lead_s`` of traffic, then ``seconds`` measured, then the
        drain; returns everything the result line and the checks need."""
        from benchmark.traffic import window_numbers
        workload = self.workload
        lead_s = float(workload["lead_s"])
        trace_dir, cut, trace_stamp = None, None, None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="benchmark_trace_")
        # The engine's frames sit in reference cycles, so their device
        # buffers wait for a generation-1 collection and are then
        # freed in bulk: every ~71 requests the process stalls (PERF.md
        # section 6).  Until the program breaks those cycles: set-up's
        # objects leave the collector's sight and every collection is
        # a full one of what is young, which frees a few frames at a
        # time.
        gc.collect()
        gc.freeze()
        gc.set_threshold(700, 1, 1)
        round_stamp = time.perf_counter()
        round_counters = self.counters()
        begin = self.traffic.start(lead_s + seconds)
        start_s = begin + lead_s
        self.traffic.sleep_until(start_s)
        self.registry.reset()
        wall_start = time.time()
        setup_s = time.perf_counter() - _PROCESS_START
        before = self.counters()
        slice_counters = {}
        if trace:
            import jax
            slice_s = min(float(workload["trace_slice_s"]), seconds)
            self.traffic.sleep_until(start_s + seconds - slice_s)
            slice_before = self.counters()
            # Host tracing of any level stalls the path that uploads
            # camera frames (a 1 s slice saw 99 % idle with it and 18 %
            # without; PERF.md section 5), and the Python tracer and
            # the HLO protos are most of a 144 MB file: the device
            # trace alone is taken; the flight recorder names the gaps.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            options.enable_hlo_proto = False
            entered = time.perf_counter()
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            trace_stamp = (entered, time.perf_counter())
            self.traffic.sleep_until(start_s + seconds)
            slice_counters = self.delta(self.counters(), slice_before)
        else:
            self.traffic.sleep_until(start_s + seconds)
        counters = self.delta(self.counters(), before)
        if trace:
            stop_started = time.perf_counter()
            jax.profiler.stop_trace()
            detail(phase="trace written",
                   seconds=time.perf_counter() - stop_started)
        self.await_round("the window's drain", self.limits["drain"],
                         strict=False)
        window_builds = self.builds_since(round_stamp)
        round_delta = self.delta(self.counters(), round_counters)
        records = [r for r in self.traffic.records()
                   if (r["sent_s"] or 0.0) >= begin - 0.01]
        numbers = window_numbers(records, start_s, seconds,
                                 int(workload["new_tokens"]))
        counters["client.answered"] = numbers["answered_inside"]
        # The engine's own spans of the frames that finished inside
        # the window, by trace id (exact, where the registry's
        # histograms have log buckets).
        frames = {entry["trace_id"]: entry
                  for entry in self.pipeline.telemetry.traces.snapshot()
                  if wall_start <= entry["finished"]
                  < wall_start + seconds}
        if trace:
            from benchmark import trace as trace_reduction
            path = trace_reduction.find_xplane(trace_dir)
            try:
                if path is not None:
                    read_started = time.perf_counter()
                    size = os.path.getsize(path)
                    cut = trace_reduction.read_xplane(path)
                    detail(phase="trace read", bytes=size,
                           seconds=time.perf_counter() - read_started)
                    if keep_cut:
                        with open(keep_cut, "w") as stream:
                            json.dump(cut, stream)
                    if not cut["devices"]:
                        cut = None      # no TPU plane: nothing to reduce
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
        return {"numbers": numbers, "records": records,
                "counters": counters, "slice_counters": slice_counters,
                "round_delta": round_delta, "setup_s": setup_s,
                "window_builds": window_builds, "cut": cut,
                "frames": frames, "trace_stamp": trace_stamp,
                "window": (self.traffic.epoch + start_s,
                           self.traffic.epoch + start_s + seconds),
                "start_s": start_s, "seconds": seconds}

    # -- checks -----------------------------------------------------------------

    def checks(self, outcome: dict) -> dict:
        from benchmark.traffic import in_order
        workload = self.workload
        records, delta = outcome["records"], outcome["round_delta"]
        ok = [r for r in records if r["status"] == "ok"]
        verdict = {
            "reference_agrees": bool(self.reference
                                     and self.reference["ok"]),
            # (a rehearsal that swaps the model serves other widths)
            "widths_match_file": (
                self.rehearse and self.config["llm_element"]
                in self.config.get("rehearse", {}))
            or not self.architecture.width_differences(
                self.config, self.llm._batcher),
            "all_answered_once_in_order":
                len(ok) == len(records) and in_order(records),
            "tokens_match_requests":
                delta.get("batcher.tokens_emitted")
                == len(ok) * int(workload["new_tokens"])
                and all(r["data"].get("text") for r in ok),
            "no_implicit_transfer":
                delta["engine.implicit_transfers"] == 0,
            "no_broken_segment": delta["engine.fused_broken"] == 0,
            "no_batcher_recovery":
                delta.get("batcher.recoveries", 0) == 0,
            "no_program_built_in_window": not outcome["window_builds"],
        }
        echo = workload.get("echo")
        if echo:
            verdict["results_echo_requests"] = all(
                r["data"].get(field) == r[how[1:]]
                for r in ok for field, how in echo.items())
        same = workload.get("same_answer")
        if same:
            answers: dict = {}
            for r in ok:
                key = tuple(
                    r["data"].get(field) % same["modulo"][field]
                    if field in same.get("modulo", {})
                    else r["data"].get(field) for field in same["key"])
                answers.setdefault(key, set()).add(json.dumps(
                    [r["data"].get(field) for field in same["fields"]],
                    sort_keys=True))
            varied = {str(key): len(seen)
                      for key, seen in answers.items() if len(seen) > 1}
            verdict["same_input_same_answer"] = not varied
            outcome["varied_answers"] = varied
        return verdict

    def stop(self):
        if self.traffic is not None:
            self.traffic.close()
        self.pipeline.stop()
        self.runtime.terminate()


def measure(bench: Bench, arguments) -> dict:
    """Warm up, run the window, reduce, check; returns the contract
    line's object."""
    from benchmark import readers, trace as trace_reduction
    from benchmark.traffic import percentile, samples_beyond
    bench.warm_up()
    outcome = bench.window(float(arguments.seconds),
                           bool(arguments.trace), arguments.keep_cut)
    numbers = outcome["numbers"]
    verdict = bench.checks(outcome)
    latencies = numbers["latencies_ms"]
    detail(workload=arguments.workload, seed=arguments.seed,
           requests=numbers["attempted"], statuses=numbers["statuses"],
           latency_samples=len(latencies),
           samples_beyond_p95=samples_beyond(len(latencies), 95),
           answered_inside_window=numbers["answered_inside"],
           generator_late_ms=numbers["generator_late_ms"],
           warmup_rounds=bench.warmup_rounds, checks=verdict,
           window_builds=outcome["window_builds"],
           varied_answers=outcome.get("varied_answers"),
           counters=outcome["counters"],
           slice_counters=outcome["slice_counters"],
           programs_built=len(bench.builds))
    end_to_end = {
        "latency_p50_ms": lambda: percentile(latencies, 50),
        "latency_p95_ms": lambda: percentile(latencies, 95),
        "tokens_per_s": lambda: numbers["tokens_per_s"],
        "setup_s": lambda: outcome["setup_s"],
    }
    metrics = {}
    device = dict(bench.device)
    peak = 0
    import jax
    for chip in jax.devices()[:int(bench.loaded["cell"]["chips"])]:
        stats = chip.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    device["memory_peak_bytes"] = peak
    result = {"correct": all(verdict.values()),
              "attempted": numbers["attempted"],
              "failed": numbers["failed"], "metrics": metrics,
              "device": device}
    if not arguments.trace:
        for metric in bench.loaded["end_to_end"]:
            if latencies or not metric["name"].startswith("latency"):
                metrics[metric["name"]] = {
                    "value": end_to_end[metric["name"]](),
                    "unit": metric["unit"]}
    else:
        context = Context(bench, numbers, outcome)
        for metric in bench.loaded["per_layer"]:
            value = context.metric(metric["name"])
            if value is not None:
                metrics[metric["name"]] = {"value": value,
                                           "unit": metric["unit"]}
        if context.notes:
            detail(reader_notes=context.notes)
        cut = outcome["cut"]
        if cut is not None:
            busy_s, window_s = trace_reduction.busy_and_window(cut)
            device["busy_s"], device["window_s"] = busy_s, window_s
            result["breakdown"] = {
                "device_ops": trace_reduction.top_device_ops(cut),
                "idle_gaps": trace_reduction.idle_gaps(
                    cut, host=context.host)}
            detail(programs_ms=trace_reduction.programs_ms(cut))
        if not device.get("busy_s") and not bench.rehearse:
            result["correct"] = False
            detail(problem="no operation ran on the device in the "
                           "traced slice, or no trace was written")
    if bench.rehearse:
        # A walk-through on the CPU: its numbers are not measurements
        # and never appear under a metric's name.
        kept = {key: result[key] for key in ("correct", "attempted", "failed")}
        result = {"rehearsal": True, **kept, "device": bench.device,
                  "metrics_walked": sorted(metrics)}
    # Each number compared beside its limit, last in the line.
    result["compared"] = {
        "reference_max_abs_diff": [bench.reference["max_abs_diff"],
                                   bench.reference["tolerance"]],
        **{name: [bool(ok), True] for name, ok in verdict.items()}}
    return result


def in_thread(bench: Bench, work, limit_s: float):
    """Run ``work()`` on a controller thread while this (the main)
    thread runs the program's event loop; its result, or its
    exception re-raised."""
    box: dict = {}

    def controller():
        try:
            box["result"] = work()
        except BaseException as error:
            box["error"] = error
            box["traceback"] = traceback.format_exc()

    thread = threading.Thread(target=controller, name="bench-controller",
                              daemon=True)
    thread.start()
    bench.runtime.run(until=lambda: not thread.is_alive(),
                      timeout=limit_s)
    if thread.is_alive():
        raise Expired(f"the run did not end within {limit_s:.0f} s")
    if "error" in box:
        print(box["traceback"], file=sys.stderr)
        raise box["error"]
    return box["result"]


def prepare(arguments) -> Bench:
    """Platform gate, compile cache, pipeline."""
    cell = load_cell(arguments.workload)["cell"]
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if not arguments.rehearse and platform != "tpu":
        print(f"benchmark: needs a TPU; jax found platform={platform!r} "
              f"({devices[0].device_kind}, {len(devices)} device(s)); "
              f"--rehearse walks a cell off the chip", file=sys.stderr)
        raise SystemExit(2)
    if not arguments.rehearse and len(devices) < int(cell["chips"]):
        print(f"benchmark: {arguments.workload} needs {cell['chips']} "
              f"chip(s), jax found {len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    from aiko_services_tpu.pipeline import setup_compilation_cache
    cache_dir = setup_compilation_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    detail(phase="start", platform=platform,
           kind=devices[0].device_kind, devices=len(devices),
           compile_cache=cache_dir, cache_entries=entries,
           rehearsal=bool(arguments.rehearse))
    return Bench(arguments.workload, arguments.seed, arguments.rehearse)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true",
                        help="walk the control flow at model: tiny on "
                             "any backend; reports no metric")
    parser.add_argument("--manifest", default=MANIFEST[0],
                        help="its data files are looked for beside it")
    parser.add_argument("--keep-cut", default=None,
                        help="also write the reduced trace (JSON) here")
    arguments = parser.parse_args(argv)
    faulthandler.enable()
    MANIFEST[0] = os.path.abspath(arguments.manifest)
    bench = prepare(arguments)
    limit_s = (sum(bench.limits.values()) + arguments.seconds + 600.0)
    try:
        result = in_thread(bench, lambda: measure(bench, arguments),
                           limit_s)
    except Expired as error:
        bench.dump(str(error))
        sys.stderr.flush()
        os._exit(3)
    except Exception:
        traceback.print_exc()
        sys.stderr.flush()
        os._exit(1)
    stopper = threading.Thread(target=bench.stop, daemon=True)
    stopper.start()
    stopper.join(timeout=60.0)
    print(json.dumps(result), flush=True)
    print("compared:", json.dumps(result["compared"]), file=sys.stderr)
    sys.stderr.flush()
    os._exit(0)             # no thread of the program may hold the exit


if __name__ == "__main__":
    sys.exit(main())
