"""Host-to-device link rate of the chip: pinned and pageable host memory.

    python3 bench/link.py [--sizes-mb 64,256,1024] [--iters 5]

Copies each size from a ``pinned_host`` array and from a pageable numpy
array to the device with ``jax.device_put``, the sweep of
benchmarks/bench_transfer.py's ``measure_h2d``, and prints one JSON line
per size and a last line with the rates at the largest size.  Refuses to
run anywhere but on a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _time(fn, iters):
    import jax

    jax.block_until_ready(fn())            # warm
    best = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best.append(time.perf_counter() - t0)
    return sorted(best)[len(best) // 2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes-mb", default="64,256,1024")
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"link.py needs a TPU; found {dev.platform!r}", file=sys.stderr)
        return 3
    pinned = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
    on_device = jax.sharding.SingleDeviceSharding(dev, memory_kind="device")
    rows = []
    for mb in (int(s) for s in args.sizes_mb.split(",")):
        n = mb << 20
        host = np.random.default_rng(0).integers(0, 255, n, np.uint8)
        t_pageable = _time(lambda: jax.device_put(host, dev), args.iters)
        on_host = jax.device_put(jnp.asarray(host), pinned)
        jax.block_until_ready(on_host)
        t_pinned = _time(lambda: jax.device_put(on_host, on_device),
                         args.iters)
        row = {"mbytes": mb, "pinned_s": t_pinned,
               "pinned_bytes_per_s": n / t_pinned, "pageable_s": t_pageable,
               "pageable_bytes_per_s": n / t_pageable}
        rows.append(row)
        print(json.dumps(row), flush=True)
        del on_host
    print(json.dumps({"device": dev.device_kind,
                      "pinned_bytes_per_s": rows[-1]["pinned_bytes_per_s"],
                      "pageable_bytes_per_s":
                          rows[-1]["pageable_bytes_per_s"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
