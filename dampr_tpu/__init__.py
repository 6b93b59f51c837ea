"""dampr_tpu — a TPU-native out-of-core dataflow/MapReduce framework.

Same capabilities and fluent API as the reference Dampr (single-machine,
pure-Python, fork+disk — reference dampr/__init__.py:1-33), re-designed
TPU-first: records batch into columnar blocks, keyed work (hashing, sorting,
grouping, folding) runs as vectorized XLA kernels, shuffles ride device
collectives on a `jax.sharding.Mesh` (see dampr_tpu.parallel), and the memory
hierarchy is HBM -> host RAM -> disk instead of RAM -> disk.

    >>> from dampr_tpu import Dampr
    >>> Dampr.memory([1, 2, 3, 4, 5]).map(lambda x: x + 1).read()
    [2, 3, 4, 5, 6]
"""

import logging

from . import settings as _settings

_settings.configure_compile_cache()

from .base import (BlockMapper, BlockReducer, Map, Mapper, Reduce, Reducer,
                   StreamMapper, StreamReducer, Streamable)
from .blocks import Block, BlockBuilder
from .dampr import (ARReduce, Dampr, PBase, PJoin, PMap, PReduce, RunStats,
                    ValueEmitter, setup_logging)
from .dataset import (BlockDataset, CatDataset, Chunker, Dataset, EmptyDataset,
                      GzipLineDataset, MemoryDataset, StreamDataset,
                      TextLineDataset)
from .graph import Graph, Source
from .inputs import MemoryInput, PathInput, TextInput, UrlsInput
from .runner import MTRunner

__version__ = "0.2.0"

__all__ = [
    "Dampr", "PBase", "PMap", "PReduce", "PJoin", "ARReduce", "ValueEmitter",
    "RunStats",
    "Mapper", "Streamable", "Map", "BlockMapper", "StreamMapper",
    "Reducer", "Reduce", "BlockReducer", "StreamReducer",
    "Graph", "Source", "MTRunner",
    "Dataset", "Chunker", "EmptyDataset", "MemoryDataset", "TextLineDataset",
    "GzipLineDataset", "CatDataset", "StreamDataset", "BlockDataset",
    "MemoryInput", "PathInput", "TextInput", "UrlsInput",
    "Block", "BlockBuilder",
    "setup_logging",
]

logging.getLogger("dampr_tpu").addHandler(logging.NullHandler())
