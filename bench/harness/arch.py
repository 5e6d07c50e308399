"""A configuration file of the benchmark, as the harness reads it.

Each file under ``bench/configs/`` is a JSON object that holds the model's
sizes under the keys of its published ``config.json`` (as run here), its
``source``, the keys changed from it (``reduced``), sizes assumed where the
source gives none (``assumed``), the deployment it stands for, and a
``serving`` group with the engine's settings for this chip.

Its ``family`` names the architecture: a module
``bench/families/<family>.py`` that reads the file and does everything of
the harness that depends on the architecture.  A family provides

* ``parse(d) -> Arch``: the file as a frozen dataclass derived from
  :class:`Arch` (hashable, since the reference compiles per configuration),
  with the family's own sizes beside the common ones;
* ``draw(a, key)``: the weights drawn from ``key`` on the device, in one
  jitted call (``harness.weights.draw_model`` draws the embedding, final
  norm and head under the keys the reference reads);
* ``program_params(w)`` and ``program_config(a)``: those weights as the
  program's parameter tree, and the program's ``ModelConfig``;
* ``layers(w, x, pos, a, fp8)``: the float32 reference's walk over every
  layer (``harness.reference`` has the embedding, head and helpers);
* ``token_flops(a, ctx)`` and ``head_flops(a)``: the work of one token
  attending to ``ctx`` keys, and of one position's logits;
* ``kv_bytes_per_token_layer(a, itemsize)`` and
  ``kv_scale_bytes_per_chunk(a)``: the stored cache of one token in one
  layer, and the f32 scales of one int8 chunk.

So a configuration of a new architecture enters as files: its
configuration, its family, its traffic and limits.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Optional

FAMILIES_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "families")
MODULE_PREFIX = "bench_family_"      # a family's name in sys.modules


class BenchError(Exception):
    """The run cannot produce a result (no chip, missing files, ...)."""


@dataclass(frozen=True)
class Arch:
    """The sizes every family has; a family's ``Arch`` adds its own."""
    family: str
    name: str
    program_arch: str          # the program's registered architecture
    layers: int
    d_model: int
    vocab: int
    norm_eps: float
    tied: bool
    dtype: str
    chunk_size: int
    stages: int
    max_batch: int
    hbm_tier_bytes: int


def common(d: dict) -> dict:
    """The fields of :class:`Arch` from a configuration file's object."""
    s = d["serving"]
    return dict(
        family=d["family"], name=d["name"], program_arch=d["program_arch"],
        layers=int(d["num_hidden_layers"]), d_model=int(d["hidden_size"]),
        vocab=int(d["vocab_size"]), norm_eps=float(d["rms_norm_eps"]),
        tied=bool(d["tie_word_embeddings"]), dtype=d["torch_dtype"],
        chunk_size=int(s["chunk_size"]), stages=int(s["stages"]),
        max_batch=int(s["max_batch"]),
        hbm_tier_bytes=int(s["hbm_tier_bytes"]))


def family(name: str, directory: str = FAMILIES_DIR) -> ModuleType:
    """The family module ``<directory>/<name>.py``, loaded once."""
    path = os.path.join(directory, name + ".py")
    mod = sys.modules.get(MODULE_PREFIX + name)
    if mod is not None and mod.__file__ == path:
        return mod
    if not os.path.isfile(path):
        raise BenchError(f"no architecture family {name!r}: {path} "
                         f"does not exist")
    spec = importlib.util.spec_from_file_location(MODULE_PREFIX + name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs, as an import would: dataclasses look
    # their module up while the family's Arch is made
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def family_of(a: Arch) -> ModuleType:
    """The family that parsed ``a``."""
    return sys.modules[MODULE_PREFIX + a.family]


def load(path: str, name: Optional[str] = None,
         families: str = FAMILIES_DIR) -> Arch:
    with open(path) as f:
        d = json.load(f)
    if name:
        d["name"] = name
    return family(d["family"], families).parse(d)
