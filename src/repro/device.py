"""Device policy: how Pallas kernels run, and where compiled code is cached.

Three rules, all resolved when they are needed and never at import:

  * ``pallas_interpret`` — Pallas kernels compile for the chip on ``tpu``
    and run in the interpreter on ``cpu`` (how the tests validate them);
    any other platform is an error rather than a silent interpreter run.
    Every kernel wrapper takes an explicit ``interpret=`` that overrides
    the rule.
  * ``enable_compile_cache`` — the persistent compilation cache lives where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads it itself), or else at
    the fixed ``<checkout>/.jax_cache``: the path is part of the cache key,
    so a directory that moves never hits.  Entry points call it once at
    start-up (``chip_smoke.py``, ``python -m repro.gateway``, the shard
    server and the shard subprocess).
  * ``refuse_cpu_fallback`` — a shard worker process on a host with a TPU
    runtime must serve on the chip.  With ``JAX_PLATFORMS`` unset, JAX
    quietly falls back to the CPU when the chip is held by another process;
    the worker raises instead, so its pool reports a typed ``WorkerDied``
    carrying the reason.  ``JAX_PLATFORMS=cpu`` asks for the CPU explicitly.
"""
from __future__ import annotations

import importlib.util
import os

import jax

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Interpret mode for a Pallas call: the caller's choice, else the
    platform's rule (compiled on tpu, interpreted on cpu)."""
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas kernel rule for platform {platform!r} (tpu compiles, "
        "cpu interprets)"
    )


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path  # JAX already took it from the environment
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def _tpu_runtime_installed() -> bool:
    return importlib.util.find_spec("libtpu") is not None


def refuse_cpu_fallback() -> None:
    """Raise if JAX fell back to the CPU on a host with a TPU runtime and
    nobody asked for the CPU (``JAX_PLATFORMS`` unset).  Opens the backend
    either way, so a process that cannot have the chip fails here."""
    if jax.default_backend() != "cpu" or os.environ.get("JAX_PLATFORMS"):
        return
    if _tpu_runtime_installed():
        raise RuntimeError(
            "JAX fell back to the CPU on a host with a TPU runtime: the chip "
            "is held by another process (one process per chip), or it failed "
            "to open. Set JAX_PLATFORMS=cpu to serve on the CPU."
        )
