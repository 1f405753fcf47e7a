#!/usr/bin/env python3
"""Measure the chip's int32 multiply-add rate on the VPU.

    python3 benchmark/tools/vpu_peak.py            # on a chip: prints the rate
    python3 benchmark/tools/vpu_peak.py --compile-only   # here: Mosaic accepts it

The verify kernels are int32 VPU work and no published VPU peak exists, so
``verify_kernel_roofline`` is held against this measurement: a Pallas kernel
that keeps four independent chains ``a = a * x + c`` of one (8, 1024) int32
block in registers, no memory traffic inside the loop. The best of several
timed calls goes into benchmark/harness/peaks.json as
``int32_mul_add_per_s`` with ``"source": "measured"`` and the PR's number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

ROWS, COLS = 8, 1024          # one block: 8 vregs of 8 x 128 int32
BLOCKS = 64
CHAINS = 4
ITERS = 16384
UNROLL = 8


def build():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref):
        x = x_ref[...]
        chains = tuple(x + k for k in range(CHAINS))

        def body(_, cs):
            for _step in range(UNROLL):     # Mosaic unrolls only by hand
                cs = tuple(c * x + 40503 for c in cs)
            return cs

        chains = jax.lax.fori_loop(0, ITERS // UNROLL, body, chains)
        out = chains[0]
        for c in chains[1:]:
            out = out ^ c
        o_ref[...] = out

    spec = pl.BlockSpec((ROWS, COLS), lambda i: (i, 0), memory_space=pltpu.VMEM)
    fn = pl.pallas_call(
        kernel, grid=(BLOCKS,), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((ROWS * BLOCKS, COLS), jnp.int32))
    return jax.jit(fn)


MUL_ADDS = ROWS * COLS * BLOCKS * CHAINS * ITERS


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--calls", type=int, default=7)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    fn = build()
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        x = jax.ShapeDtypeStruct((ROWS * BLOCKS, COLS), jnp.int32,
                                 sharding=SingleDeviceSharding(topo.devices[0]))
        text = fn.lower(x).compile().as_text()
        print(json.dumps({"compiled": True, "mul_adds": MUL_ADDS,
                          "tpu_custom_call": "tpu_custom_call" in text}))
        return 0
    if jax.default_backend() != "tpu":
        print("vpu_peak: needs a TPU", file=sys.stderr)
        return 2
    x = (jnp.arange(ROWS * BLOCKS * COLS, dtype=jnp.int32) * 2654435 + 1
         ).reshape(ROWS * BLOCKS, COLS)
    fn(x).block_until_ready()
    times = []
    for _ in range(args.calls):
        t0 = time.monotonic()
        fn(x).block_until_ready()
        times.append(time.monotonic() - t0)
    best = min(times)
    d = jax.devices()[0]
    print(json.dumps({"device_kind": d.device_kind, "mul_adds": MUL_ADDS,
                      "seconds": sorted(times),
                      "int32_mul_add_per_s": MUL_ADDS / best}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
