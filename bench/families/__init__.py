"""What the benchmark knows of a model family, one file per family.

A configuration file names its family under ``"reference"``, and
:func:`family` imports ``families/<reference>.py``, as ``run.reader`` finds
``metrics/<name>.py``. Everything that depends on the family lives in that
file; ``harness.py``, ``weights.py``, ``reference.py`` and ``counts.py`` keep
what every family shares and call the family for the rest. A family file
defines:

``engine_fields(cfg)``
    The engine's ``ModelConfig`` fields of the family (heads, widths, mixer,
    norm). ``harness.model_config`` sets the shared ones: name, depth,
    width, vocabulary, tied embeddings, dtype.
``layout(cfg)``
    Name -> shape of every weight as the engine's parameter tree holds it:
    ``embed``, ``unembed``, ``final_norm`` outside the layers, layer weights
    under ``slots.0.`` with a leading layer axis.
``DTYPES`` (optional)
    Name -> dtype of the weights that are not bfloat16.
``draw(key, name, shape, dtype)`` (optional)
    One weight, or one layer of it, from its key; ``weights.draw`` where the
    family defines none.
``layer(x, w, cfg, quant)``, ``head(x, top, cfg, quant)``
    The plain reference: one layer over a sequence ``x`` [T, D] in float32,
    and the output head's logits. ``w`` holds one layer's weights by their
    last name; ``top`` the weights outside the layers. Every matmul goes
    through ``reference.dot``, so that ``quant`` puts a control in place;
    both are traced by ``reference.logits``.
``layer_params(cfg)``, ``params(cfg)``, ``prefill_flops(cfg, n)``,
``decode_flops(cfg, seen)``, ``decode_bytes(cfg, seen)``
    The counts, by the rules of ``counts.py``.

A new family adds one file here. A new configuration then adds only new
files (its configuration, its family if new, its limits, its traffic if new)
and entries in ``BENCHMARK.json``: no file that is already there is edited.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

DIR = Path(__file__).resolve().parent
REQUIRED = ("engine_fields", "layout", "layer", "head", "layer_params", "params",
            "prefill_flops", "decode_flops", "decode_bytes")


def family(cfg: dict):
    """The family module that the configuration names under ``reference``."""
    path = DIR / f"{cfg['reference']}.py"
    if not path.exists():
        raise FileNotFoundError(
            f"configuration {cfg.get('name')!r} names the family "
            f"{cfg['reference']!r}, but there is no family file {path}")
    return _load(path)


@functools.lru_cache
def _load(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_family_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in REQUIRED if not hasattr(mod, f)]
    if missing:
        raise AttributeError(f"family file {path} does not define {missing}")
    return mod
