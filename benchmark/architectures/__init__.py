"""Model families, one module each, found by the name a configuration
file gives under ``"architecture"``: ``architectures/<name>.py`` beside
the manifest or, failing that, here.  The module is all the harness
knows of a family; nothing else in ``benchmark/`` names one.  It gives:

``check_reference(batcher, seed, spec) -> dict``
    the path the batcher serves with against the family's own plain
    float32 forward pass, on one prompt made from the seed; ``spec`` is
    the file's ``reference``.  Returns ``max_abs_diff``,
    ``mean_abs_diff``, ``logit_std``, ``positions``, ``argmax_agree``.
``width_differences(config, batcher) -> list``
    ``(key, published, served)`` wherever what is served differs from
    the file's published keys (per-layer lists included, where the
    family has them).
``element_parameters(config) -> dict``
    the parameters the file hands the LLM element of its definition
    (the family's element class, named by ``deploy.local.module``,
    builds the model from them).
``decode_step(config, rows, context_tokens) -> {"bytes", "operations"}``
    what one decode step over ``rows`` live sequences at a mean context
    must stream and compute, from the published widths alone.

There is no default: a configuration without the key is an error."""

import importlib.util
import os
import sys

PIECES = ("check_reference", "width_differences", "element_parameters",
          "decode_step")


def load(config: dict, directories):
    """The architecture module of a configuration file, looked for as
    ``<directory>/architectures/<name>.py`` in ``directories``."""
    name = config.get("architecture")
    where = [os.path.join(directory, "architectures")
             for directory in directories]
    if not name:
        raise SystemExit(
            f"benchmark: configuration {config.get('name')!r} has no "
            f"\"architecture\" key; it names the file <name>.py in "
            f"{' or '.join(where)} that holds the model family's "
            f"reference, width check, element parameters and step count")
    module_name = f"{__name__}.{name}"
    if module_name in sys.modules:
        return sys.modules[module_name]
    for directory in where:
        path = os.path.join(directory, f"{name}.py")
        if os.path.isfile(path):
            spec = importlib.util.spec_from_file_location(module_name,
                                                          path)
            module = importlib.util.module_from_spec(spec)
            sys.modules[module_name] = module
            try:
                spec.loader.exec_module(module)
                missing = [piece for piece in PIECES
                           if not callable(getattr(module, piece, None))]
                if missing:
                    raise SystemExit(f"benchmark: architecture {name!r} "
                                     f"({path}) lacks {missing}")
            except BaseException:
                del sys.modules[module_name]
                raise
            return module
    raise SystemExit(
        f"benchmark: \"architecture\": {name!r} of configuration "
        f"{config.get('name')!r}: no file {name}.py in "
        f"{' or '.join(where)}")
