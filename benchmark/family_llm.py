"""The element class of a configuration whose family the program's LLM
element builds itself, from ``family`` + ``widths`` (the architecture
file's ``element_parameters``): the program's ``LLM`` as it is, plus
the two things every cell of this benchmark adds around it --
``ignore_eos`` (random weights know no end of sequence) and the
first-token-join programs built in set-up."""

from __future__ import annotations

import json
import time

from aiko_services_tpu.elements.llm import LLM

from benchmark.elements import ConfiguredLLM, _NoStopTokenizer

if "family" not in LLM._MODEL_PARAMS:
    raise ImportError("this program's LLM element takes no `family` "
                      "parameter: it serves its presets only")


class FamilyLLM(LLM):
    _MODEL_PARAMS = LLM._MODEL_PARAMS + ("ignore_eos",)

    def _ensure_model(self, settings: dict | None = None):
        if self._batcher is not None:
            return
        settings = dict(settings or self._resolve_model_params())
        ignore_eos = settings.pop("ignore_eos", False)
        started = time.perf_counter()
        super()._ensure_model(settings)
        if ignore_eos:
            self._tokenizer = _NoStopTokenizer()
        built = time.perf_counter()
        ConfiguredLLM._warm_first_token_joins(self)
        print(json.dumps({"note": "detail", "phase": "model build",
                          "seconds": built - started,
                          "first_token_joins_s":
                              time.perf_counter() - built}), flush=True)
