"""Launcher kinds, one module each, chosen by a configuration's `topology`."""
import importlib


def load(kind: str):
    try:
        return importlib.import_module(f"benchmarks.topologies.{kind}")
    except ModuleNotFoundError:
        raise SystemExit(f"benchmark: no launcher for topology {kind!r}")
