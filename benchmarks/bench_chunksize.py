"""§2.3 "too many queries" microbenchmark.

The paper's table: reconstructing a ~100K-record version from Cassandra takes
65.42 s with per-record gets and 0.56 s with 10000-record chunks.  We
reproduce the *shape* of that curve (monotone ≫1× improvement with chunk
size) against (a) the instrumented InMemoryKVS with the Cassandra-like
latency model and (b) the real ShardedDeviceKVS gather path.
"""
from __future__ import annotations

import numpy as np

from repro.core import DatasetSpec, generate
from repro.core.kvs import InMemoryKVS, ShardedDeviceKVS

from .common import emit, main, save_json, timed


def run():
    n_records = 20_000
    record_size = 100
    rng = np.random.default_rng(0)
    payloads = [rng.integers(0, 256, record_size, dtype=np.uint8).tobytes()
                for _ in range(n_records)]

    out = {}
    for chunk_records in (1, 10, 100, 1000, 10000):
        kvs = InMemoryKVS()
        dev = ShardedDeviceKVS(slot_bytes=max(4096, chunk_records * record_size))
        n_chunks = n_records // chunk_records
        for c in range(n_chunks):
            blob = b"".join(payloads[c * chunk_records:(c + 1) * chunk_records])
            kvs.put(f"c{c}", blob)
            dev.put(f"c{c}", blob)
        keys = [f"c{c}" for c in range(n_chunks)]

        kvs.stats.reset()
        if chunk_records == 1:
            kvs.multiget_naive(keys)       # the naive per-record pattern
        else:
            kvs.multiget(keys)
        sim_s = kvs.stats.n_values * 5e-4 + kvs.stats.bytes_fetched / 200e6

        _, real_s = timed(dev.multiget, keys)
        out[chunk_records] = {"simulated_s": sim_s, "device_gather_s": real_s,
                              "kvs_values": kvs.stats.n_values}
        emit(f"chunksize/{chunk_records}", real_s * 1e6,
             f"simulated_cassandra_s={sim_s:.3f}")

    speedup = out[1]["simulated_s"] / out[10000]["simulated_s"]
    emit("chunksize/speedup_1_to_10000", 0.0,
         f"{speedup:.0f}x (paper: 65.42/0.56 = 117x)")
    save_json("bench_chunksize", out)
    return out


if __name__ == "__main__":
    main(run)
