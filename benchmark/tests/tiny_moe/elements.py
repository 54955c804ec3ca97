"""The ``tiny_moe`` family's element class: the program's LLM element,
its model chosen from the published keys that the family's architecture
file hands over (``published``: numbers and a per-layer list)."""

from benchmark.elements import ConfiguredLLM


class TinyMoeLLM(ConfiguredLLM):
    _MODEL_PARAMS = ConfiguredLLM._MODEL_PARAMS + ("published",)

    def _ensure_model(self, settings: dict | None = None):
        if self._batcher is not None:
            return
        settings = dict(settings or self._resolve_model_params())
        published = settings.pop("published")
        if set(published["mlp_layer_types"]) != {"sparse"}:
            raise ValueError("tiny_moe: every layer is sparse")
        # The one preset of the program that holds these widths (the
        # architecture's width check holds the served model to them).
        super()._ensure_model({
            **settings, "model": "tiny-moe",
            "vocab_size": int(published["vocab_size"])})
