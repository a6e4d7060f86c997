"""Process-level runtime choices, each decided in one place.

* `pallas_interpret` — whether Pallas kernels run in interpret mode,
  derived from the platform JAX runs on: the emulator on the CPU, the
  compiled kernel on the TPU, an error anywhere else. No config or call
  site selects it, so a chip run can never time the emulator by
  accident.
* `enable_compile_cache` — JAX's persistent compilation cache, switched
  on by the entry points (`chip_smoke.py`, `repro.launch.serve`, the
  offline CLI) before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout root (src/repro/runtime.py -> ../..)
CHECKOUT_ROOT = Path(__file__).resolve().parents[2]


def pallas_interpret() -> bool:
    """True on the CPU (Pallas interpret mode), False on the TPU
    (compiled Mosaic kernels). Any other platform raises: the kernels
    are written for the TPU, and emulating them elsewhere would make a
    measurement there meaningless."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas mode for platform {platform!r}: the "
                       f"kernels compile for 'tpu' and are emulated on "
                       f"'cpu' only")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing is changed; otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache`` (the path is part of the cache key,
    so it must not move between runs)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
