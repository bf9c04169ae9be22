"""Data collections & distributions (SURVEY.md §2.6)."""
from .collection import DataCollection, DictCollection, LocalArrayCollection
from .matrix import (BlockColumnCyclic, SymTwoDimBlockCyclic,
                     SymTwoDimBlockCyclicBand, TiledMatrix,
                     TwoDimBlockCyclic, TwoDimBlockCyclicBand,
                     TwoDimTabular, VectorTwoDimCyclic)
from .redistribute import redistribute, redistribute_ptg, reshard_array
from .subtile import SubtileView
from . import ops

__all__ = [
    "DataCollection", "DictCollection", "LocalArrayCollection", "TiledMatrix",
    "TwoDimBlockCyclic", "BlockColumnCyclic", "SymTwoDimBlockCyclic",
    "TwoDimBlockCyclicBand",
    "SymTwoDimBlockCyclicBand",
    "TwoDimTabular", "VectorTwoDimCyclic", "redistribute", "redistribute_ptg", "reshard_array",
    "ops", "SubtileView",
]
