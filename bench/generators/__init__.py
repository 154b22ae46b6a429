"""Traffic generators, one module per kind, named by a mix's ``generator``."""

import importlib


def load_generator(name: str):
    return importlib.import_module(f"bench.generators.{name}")
