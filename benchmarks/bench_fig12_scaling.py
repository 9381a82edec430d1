"""Fig. 12: weak scalability — double the devices AND the data, measure Q1/Q3.

The paper doubles a Cassandra cluster 1→16 nodes while doubling versions; we
shard the ShardedDeviceKVS over sub-meshes of 1→16 of the process's own
devices (as many as it has) and scale the version count with the device
count.  Claim: query times grow mildly (span growth), i.e. weak scaling
holds.  Every point runs in this process; a failed point fails the suite.
"""
from __future__ import annotations

import time

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core import DatasetSpec, RStore, RStoreConfig, generate
from repro.core.kvs import ShardedDeviceKVS

from .common import emit, main, save_json


def _point(devices) -> dict:
    ndev = len(devices)
    spec = DatasetSpec(n_versions=40 * ndev, n_base_records=400,
                       pct_update=0.1, record_size=256, payloads=True,
                       branch_prob=0.05, seed=21)
    g = generate(spec)
    kvs = ShardedDeviceKVS(slot_bytes=32 * 1024, n_slots=256,
                           mesh=Mesh(np.asarray(devices), ("data",)))
    rs = RStore(RStoreConfig(algorithm="bottom_up", capacity=24 * 1024,
                             batch_size=10**9), kvs=kvs)
    rs.graph = g
    rs._grow_r2c()
    rs.build()

    rng = np.random.default_rng(0)
    vids = rng.choice(g.versions, 8)
    keys = rng.choice(g.store.keys(), 8)
    rs.get_version(int(vids[0]))          # warmup (compile the gather)
    t0 = time.perf_counter()
    spans = [rs.get_version(int(v))[1].chunks_fetched for v in vids]
    q1 = (time.perf_counter() - t0) / len(vids)
    t0 = time.perf_counter()
    kspans = [rs.get_evolution(int(k))[1].chunks_fetched for k in keys]
    q3 = (time.perf_counter() - t0) / len(keys)
    return {"ndev": ndev, "versions": spec.n_versions, "q1_s": q1,
            "q3_s": q3, "avg_version_span": float(np.mean(spans)),
            "avg_key_span": float(np.mean(kspans))}


def run():
    devices = jax.devices()
    out = {}
    for ndev in (1, 2, 4, 8, 16):
        if ndev > len(devices):
            break
        rec = _point(devices[:ndev])
        out[ndev] = rec
        emit(f"fig12/ndev{ndev}", rec["q1_s"] * 1e6,
             f"versions={rec['versions']} vspan={rec['avg_version_span']:.1f} "
             f"q3_us={rec['q3_s']*1e6:.0f} kspan={rec['avg_key_span']:.1f}")
    save_json("bench_fig12_scaling", out)
    return out


if __name__ == "__main__":
    main(run)
