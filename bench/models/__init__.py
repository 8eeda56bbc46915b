"""Model families, one file each: ``bench/models/<architecture>.py``.

A configuration names its family the way Hugging Face names the class to
build, by ``model["architectures"][0]``.  A family file exports
``program_config(model, name)``, ``make_params(model, seed)``,
``hparams(model)``, ``lm_logits(params, tokens, hp, quant=False)``,
``prefill_flops(model, length)`` and ``decode_flops(model, position)``;
everything the benchmark knows of one architecture lives there.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

MODELS_DIR = Path(__file__).resolve().parent


def known(models_dir: Path = MODELS_DIR) -> list:
    """The architectures that have a family file in ``models_dir``."""
    return sorted(p.stem for p in Path(models_dir).glob("*.py")
                  if not p.stem.startswith("_"))


def family(model: dict, models_dir: Path = MODELS_DIR):
    """The family module of a configuration's ``model`` block.  A block
    that names no architecture, or one with no file, is refused: there is
    no default family."""
    names = model.get("architectures")
    if not names:
        raise KeyError(f"the model block names no 'architectures'; known: "
                       f"{known(models_dir)}")
    path = Path(models_dir) / f"{names[0]}.py"
    if not path.is_file():
        raise KeyError(f"no model family {names[0]!r} in {models_dir}; "
                       f"known: {known(models_dir)}")
    return _load(path.resolve())


@functools.lru_cache(maxsize=None)
def _load(path: Path):
    # one module per file, so that its jitted functions keep their caches
    spec = importlib.util.spec_from_file_location(
        "bench_model_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
