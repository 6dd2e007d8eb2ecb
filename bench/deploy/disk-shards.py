"""Disk-based deployment sharded over chips: the collection written through
``Hercules.create`` (the chunked build) to a store in a temporary
directory, and served from it by ``dist-ooc`` over ``shards`` chips, each
streaming its own leaf-run range under ``memory_budget_mb``. The store is
removed on close.

Configuration keys: those of ``bench/deploy/disk.py``, and ``shards`` (the
number of shards, one chip each, whatever number of chips JAX sees).
"""
from __future__ import annotations

import os
import shutil
import tempfile

import jax
import numpy as np

from repro.api import (ArrayChunkSource, Hercules, KnnServeConfig,
                       KnnServeEngine)

from bench import registry
from bench.serving import index_config

Deployment = registry.load_module("deploy", "disk").Deployment


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
                                  memory_budget_mb=cfg["memory_budget_mb"],
                                  shards=int(cfg["shards"]))
    except BaseException:
        shutil.rmtree(workdir, ignore_errors=True)
        raise
    server = KnnServeEngine(engine, KnnServeConfig(**cfg.get("serve", {})))
    return Deployment(server, engine, store, workdir)
