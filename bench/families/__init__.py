"""Model families: what the harness must know of a configuration's block.

A configuration file names its family under ``"family"``; without the key
it is ``"transformer"``.  ``families/<family>.py`` supplies what the
generic harness (``cell.py``, ``run.py``, ``check.py``, the MFU readers)
calls, and nothing more:

* ``dims(conf)``: a frozen, hashable shape object read from the
  configuration's file, with at least ``n_layers``, ``vocab`` and
  ``padded_vocab`` and whatever the family's weights, reference and
  counts need;
* ``arch_config(cell, tiny=False)``: the program's ``ArchConfig`` with the
  family's own stages, every published width checked against the file;
* ``tiny_dims(cell, cfg)``: the shapes of a ``-tiny`` program config, for
  CPU tests of the harness;
* ``served_params(dims, seed, dtype)``: the engine's parameter tree made on
  the device from the seed, one ``stage<i>`` entry per program stage;
* ``decode_step_flops(dims, contexts)`` and
  ``prefill_chunk_flops(dims, start, n)``: model FLOPs of a decode step
  over rows at the given context lengths, and of one prompt chunk.

The plain reference is chosen apart, by the configuration's
``"reference"`` key (``reference/<name>.py``, ``logit_gaps``), and makes
its float32 weights from the seed for itself.  A new family is a new
module here, a new reference, and a configuration that names both.
"""
from __future__ import annotations

import importlib

DEFAULT = "transformer"


def family_of(conf: dict):
    """The family module a configuration names."""
    return importlib.import_module(f"{__name__}.{conf.get('family', DEFAULT)}")
