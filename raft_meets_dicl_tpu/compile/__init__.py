"""Compiled-program registry + AOT export (ROADMAP item 5).

Public surface:

- :class:`ProgramKey` / :class:`Program` / :class:`ProgramRegistry`,
  ``registry()``, ``reset()``, ``register_step()`` — one constructor for
  every jitted train/eval step in the system (``registry`` module);
- ``enable_aot()`` / ``disable_aot()`` / ``aot_enabled()`` /
  ``programs_dir()`` — the serialized-executable store that lets a
  repeat boot of the same config start stepping with zero compiles
  (``aot`` module). CLI entry points call ``enable_aot()``;
  ``RMD_AOT=0`` opts out, ``RMD_AOT_DIR`` relocates the store.
"""

from . import aot, owners
from .aot import (
    aot_enabled, artifact_path, disable_aot, enable_aot, fetch, fingerprint,
    publish,
    programs_dir,
)
from .registry import (
    Program, ProgramKey, ProgramRegistry, effective_args_key, flag_items,
    inference_key, notes_flag, register_step, registry, reset,
    shape_signature, static_args_key, unstable,
)

__all__ = [
    "aot", "owners",
    "Program", "ProgramKey", "ProgramRegistry",
    "effective_args_key", "flag_items", "inference_key", "notes_flag",
    "register_step", "registry", "reset", "shape_signature",
    "static_args_key", "unstable",
    "aot_enabled", "artifact_path", "disable_aot", "enable_aot",
    "fetch", "publish",
    "fingerprint", "programs_dir",
]
