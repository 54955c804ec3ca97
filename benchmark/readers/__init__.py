"""Reader kinds, one file each, found by file name: a per-layer metric's
file (``benchmark/layer_metrics/<metric>.json``) names its ``kind`` and
``args``; ``read(args, ctx)`` returns the number, or None when there
is nothing to read (the harness then leaves the metric out).

``ctx`` (``benchmark.run.Context``) offers: ``counters`` and
``slice_counters`` (the program's counts over the window and over the
traced slice), ``quantile(name, q, labels)`` (the telemetry registry
since the window opened), ``label_values(name, label)``,
``client_latencies_ms``, ``cut`` (the reduced trace, None untraced),
``window`` and ``trace_stamp`` (``perf_counter`` seconds: the measured
window; just before ``start_trace`` was entered and just after it
returned), ``host`` (host spans on the cut's clock: None until
``idle_by_host_span`` has laid the recorder on it), ``config``,
``architecture`` (the configuration's family, ``benchmark/
architectures``), ``workload``, ``peaks`` and ``metric(name)`` (another
per-layer metric, by name)."""

import importlib


def load(kind: str):
    """The reader module of a kind; an unknown kind is an error."""
    return importlib.import_module(f"{__name__}.{kind}")
