"""``repro.launch`` — runnable drivers (each is a ``python -m`` entry point).

``train``   elastic production training: checkpoint/restart + mesh-epoch
            recovery from injected node failures.
``serve``   serving driver: legacy static batch or continuous batching
            with paged KV, Poisson arrivals, and governor-priced slack.
``dryrun``  AOT sweep: lower + compile every (arch x shape x mesh) cell.
``mesh``    production/host mesh constructors.
``compile_cache``  where the persistent compilation cache lives.

Submodules import jax and are loaded lazily (PEP 562) so that
``import repro.launch`` stays cheap for tooling.
"""
import importlib

_SUBMODULES = ("compile_cache", "dryrun", "mesh", "serve", "train")

__all__ = list(_SUBMODULES)


def __getattr__(name: str):
    if name in _SUBMODULES:
        return importlib.import_module(f"repro.launch.{name}")
    raise AttributeError(f"module 'repro.launch' has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_SUBMODULES))
