"""One module per architecture: everything the harness knows of a model's
equations and sizes, found by the configuration file's ``arch``.

Each module in this package lists the ``arch`` ids it serves in ``ARCHS``
and provides:

- ``program_config(cfg)``: the program's ``ModelConfig`` for the sizes in
  ``cfg``;
- ``shapes(cfg)``: ``{leaf path: (shape, dtype, scale)}`` of the weights
  the benchmark draws (``weights.py``), and ``nest(flat)``: those leaves
  in the engine's parameter tree, with as many segments as it has;
- ``Reference(cfg, params)``: the plain float32 forward, with
  ``hidden(seqs, fp8, block)`` and ``logits(hall, rows, fp8)``
  (``reference.served_gaps`` reads any such object);
- the work counts behind the rooflines and MFU readings (``work.py``):
  ``moe_ffn``, ``decode_attn``, ``token_flops``, ``prefill_flops``;
- counts by kind of layer, ``attn_layers(cfg)`` and ``moe_layers(cfg)``;
  ``experts(cfg)``: the experts held here, the experts routed over and the
  experts each token takes; ``vocab(cfg)``: the ids the traffic draws;
- ``tiny(cfg)``: the configuration cut to CPU size for the tests, with
  check limits set from readings at that size.

Nothing else in the harness reads a key of the configuration file other
than ``arch``, ``serving``, ``check`` and ``torch_dtype``. Adding an
architecture adds a module here; no list is edited.
"""

from __future__ import annotations

import functools
import importlib
import pkgutil


@functools.cache
def modules() -> tuple:
    """Every architecture module in this package."""
    return tuple(importlib.import_module(f"{__name__}.{m.name}")
                 for m in pkgutil.iter_modules(__path__))


def find(cfg: dict):
    """The module that serves ``cfg["arch"]``."""
    for mod in modules():
        if cfg["arch"] in mod.ARCHS:
            return mod
    known = sorted(a for mod in modules() for a in mod.ARCHS)
    raise KeyError(f"no module under benchmarks/chip/arch serves arch "
                   f"{cfg['arch']!r}; known: {known}")
