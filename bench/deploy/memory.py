"""HBM-resident deployment: the collection built into an in-memory index
through ``make_backend`` and served by ``QueryEngine`` + ``KnnServeEngine``.

Configuration keys: ``backend`` (a memory backend name), ``build``
(``BuildConfig`` fields), ``search`` (``SearchConfig`` fields) and
``serve`` (``KnnServeConfig`` fields).
"""
from __future__ import annotations

import dataclasses

import jax

from repro.api import (KnnServeConfig, KnnServeEngine, QueryEngine,
                       make_backend)

from bench.serving import index_config


@dataclasses.dataclass
class Deployment:
    server: KnnServeEngine
    engine: QueryEngine

    def close(self) -> None:
        self.server = self.engine = None


def setup(cfg: dict, data: jax.Array, part) -> Deployment:
    with part("build"):
        backend = make_backend(cfg["backend"], data,
                               index_config=index_config(cfg))
        index = getattr(backend, "index", None)
        jax.block_until_ready((index.tree, index.layout) if index is not None
                              else backend)
    engine = QueryEngine(backend)
    server = KnnServeEngine(engine, KnnServeConfig(**cfg.get("serve", {})))
    return Deployment(server, engine)
