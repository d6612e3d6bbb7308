"""Pluggable EDM execution engines (DESIGN.md SS5).

Usage::

    from repro import engine
    eng = engine.get_engine(cfg.engine)      # cfg.engine is a str key
    idx, sqd = eng.knn_tables(Vq, Vc, k, exclude_self=True, cfg=cfg)

Registering a new backend is one call::

    engine.register(MyEngine())

and every consumer (phase-1 simplex, phase-2 CCM, benchmarks) picks it up
through ``EDMConfig(engine="my-engine")``.
"""
from __future__ import annotations

from repro.engine.base import Engine
from repro.engine.pallas import PallasEngine, PallasInterpretEngine
from repro.engine.reference import ReferenceEngine

_REGISTRY: dict[str, Engine] = {}


def register(eng: Engine) -> Engine:
    """Register an engine instance under its ``name`` (last one wins)."""
    if not eng.name or eng.name == "base":
        raise ValueError("engine must define a unique non-default .name")
    _REGISTRY[eng.name] = eng
    return eng


def get_engine(name: str) -> Engine:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown engine {name!r}; available: {sorted(_REGISTRY)}"
        ) from None


def available_engines() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


register(ReferenceEngine())
register(PallasEngine())
register(PallasInterpretEngine())

__all__ = [
    "Engine",
    "PallasEngine",
    "PallasInterpretEngine",
    "ReferenceEngine",
    "available_engines",
    "get_engine",
    "register",
]
