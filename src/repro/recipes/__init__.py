"""Recipe registry: declarative specs for every paper benchmark.

Importing this package registers all built-in recipes; list them with
``python -m repro.run --list`` or :func:`names`.
"""
from .base import RECIPES, Recipe, RunOptions, get, names, register

# importing the catalog modules registers their recipes
from . import (box, dag, hypergrid, ising,  # noqa: F401  (side effects)
               lm, phylo, seqs)

__all__ = ["Recipe", "RunOptions", "RECIPES", "register", "get", "names"]
