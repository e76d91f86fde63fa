"""What depends on a configuration's architecture, one module each.

A configuration names its architecture by its ``reference`` key, the name
of its plain reference (``bench/references/<reference>.py``).  The module
``bench/archs/<reference>.py`` holds the rest of what that architecture
decides, for the harness's generic code to ask by name:

* ``model_config(cfg)``: the serving program's config object;
* ``fan_in(cfg)``: the input width of each ternary linear, by the name of
  the weight that owns its ``codes`` and ``gamma``;
* ``leaf(names, shape, dtype, key)``, optional: the random value of a
  served leaf that ``bench/weights.py``'s generic rules do not cover, or
  None;
* ``weight_pass_bytes(cfg)``, ``decode_token(cfg, context)`` and
  ``prompt_ops(cfg, length)``: the work counts ``bench/counts.py`` gives
  the per-layer metrics, with the meaning its docstring states.
* ``SCOPES``, optional: the ``jax.named_scope`` names its served programs
  carry beyond ``bench.trace_scopes.SCOPES``, so that a per-layer metric
  can read the device time under them.

So a new architecture adds ``bench/archs/<r>.py`` and
``bench/references/<r>.py`` and edits no file of the harness.
"""

from __future__ import annotations

import importlib

from bench.trace_scopes import SCOPES


def arch(cfg: dict):
    """The architecture module of ``cfg``, found by its ``reference``."""
    name = f"{__name__}.{cfg['reference']}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise LookupError(
            f"configuration {cfg.get('name')!r} names the architecture "
            f"{cfg['reference']!r}, and bench/archs/{cfg['reference']}.py, "
            "which would describe it, does not exist") from None


def scopes(cfg: dict) -> tuple:
    """The name scopes a trace of ``cfg``'s programs is reduced by."""
    return SCOPES + tuple(getattr(arch(cfg), "SCOPES", ()))
