"""Builds the deployment a configuration file describes, and loads it.

One general builder covers the two placements the program has: full
replication (``"placement": "full"``, ``BigsetCluster(n_val)``) and the
partitioned ring (``"placement": "ring"``, ``Ring.build`` over ``vnodes``
with ``ring_creation_size`` partitions).  A configuration that needs
another shape ships ``bench/configs/<name>.py`` with a
``build(cfg) -> cluster`` function, which is used in its place.
"""
from __future__ import annotations

import importlib.util
import os
from dataclasses import dataclass
from typing import Sequence


@dataclass
class Deployment:
    cluster: object
    service: object
    client: object
    r: int


def build_cluster(cfg: dict):
    from repro.cluster.clusters import BigsetCluster
    from repro.cluster.placement import Ring

    durable = bool(cfg.get("durable", False))
    group_depth = int(cfg.get("group_depth", 8))
    if cfg["placement"] == "full":
        return BigsetCluster(cfg["n_val"], durable=durable,
                             group_depth=group_depth)
    if cfg["placement"] == "ring":
        ring = Ring.build([f"vnode{i}" for i in range(cfg["vnodes"])],
                          factor=cfg["n_val"],
                          n_partitions=cfg["ring_creation_size"])
        return BigsetCluster(ring=ring, durable=durable,
                             group_depth=group_depth)
    raise ValueError(f"unknown placement {cfg['placement']!r}")


def deploy(cfg: dict, config_dir: str, name: str) -> Deployment:
    """The cluster behind a ``BigsetService`` with its default
    ``ServiceConfig``, and one ``BigsetClient``."""
    from repro.serve.bigset_service import BigsetClient, BigsetService

    custom = os.path.join(config_dir, f"{name}.py")
    if os.path.exists(custom):
        spec = importlib.util.spec_from_file_location(
            f"bench_config_{name}", custom)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        cluster = mod.build(cfg)
    else:
        cluster = build_cluster(cfg)
    service = BigsetService(cluster)
    return Deployment(cluster, service, BigsetClient(service),
                      int(cfg["r"]))


def populate(dep: Deployment, sets: Sequence, batch: int) -> None:
    """For each set (a :class:`bench.traffic.SetData`), insert its elements
    and then remove its removed ones through ``client.batch``, ``batch``
    operations to a request."""
    client = dep.client
    for s in sets:
        for base in range(0, len(s.elements), batch):
            client.batch(s.name, [["add", el]
                                  for el in s.elements[base:base + batch]])
        for base in range(0, len(s.removed), batch):
            chunk = s.removed[base:base + batch]
            results = client.batch(s.name, [["remove", el] for el in chunk])
            if not all(r.get("removed") for r in results):
                raise RuntimeError(
                    f"set-up: a remove in batch {base // batch} of set "
                    f"{s.name!r} removed nothing")
