"""Disk-based deployment: the collection written through
``Hercules.create`` (the chunked build) to a store in a temporary
directory, and served from it by ``Hercules.engine`` under a memory budget
through ``KnnServeEngine``. The store is removed on close.

Configuration keys: those of ``bench/deploy/memory.py``, and
``build_chunk`` (series per build chunk) and ``memory_budget_mb``.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import tempfile

import jax
import numpy as np

from repro.api import (ArrayChunkSource, Hercules, KnnServeConfig,
                       KnnServeEngine, QueryEngine)

from bench.serving import index_config


@dataclasses.dataclass
class Deployment:
    server: KnnServeEngine
    engine: QueryEngine
    store: Hercules
    workdir: str

    def close(self) -> None:
        self.server = self.engine = None
        try:
            self.store.close()
        finally:
            shutil.rmtree(self.workdir, ignore_errors=True)


def setup(cfg: dict, data: jax.Array, part) -> Deployment:
    with part("stage"):
        host = np.asarray(data)
    workdir = tempfile.mkdtemp(prefix="bench-store-")
    try:
        with part("build"):
            store = Hercules.create(
                os.path.join(workdir, "index"), index_config(cfg),
                data=ArrayChunkSource(host, int(cfg["build_chunk"])))
        del host
        with part("open"):
            engine = store.engine(cfg["backend"],
                                  memory_budget_mb=cfg["memory_budget_mb"])
    except BaseException:
        shutil.rmtree(workdir, ignore_errors=True)
        raise
    server = KnnServeEngine(engine, KnnServeConfig(**cfg.get("serve", {})))
    return Deployment(server, engine, store, workdir)
