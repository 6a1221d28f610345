#!/usr/bin/env python3
"""Write the optimized HLO of a configuration's single-chip triples step.

    python3 benchmark/tools/step_hlo.py <out_dir> [--config uniform8x10k]

``--config`` names a file of ``benchmark/configs`` or is a path to one.
Builds the configuration's spaces on one single-chip bucket, runs a few
moving ticks from a fixed seed (so the triples cap has grown as it does
in a run), then lowers and compiles the bucket's step program
(``jit_aoi_step_tri``) at those shapes and writes its ``as_text()`` to
``<out_dir>/aoi_step_tri.hlo.txt``.  A device trace names operations
``jit_aoi_step_tri(…):%fusion.N``; the text says what each computes.
Run it on the chip: the CPU compiles another program.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out_dir")
    ap.add_argument("--config", default="uniform8x10k")
    ap.add_argument("--ticks", type=int, default=4)
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    import numpy as np

    import jax
    import jax.numpy as jnp

    from goworld_tpu.engine import aoi as A

    path = args.config if args.config.endswith(".json") else os.path.join(
        REPO, "benchmark", "configs", args.config + ".json")
    with open(path) as f:
        cfg = json.load(f)
    cap, n = int(cfg["capacity"]), int(cfg["entities_per_space"])
    world, radius = float(cfg["world"]), float(cfg["radius"])
    eng = A.AOIEngine(default_backend="tpu")
    hs = [eng.create_space(cap) for _ in range(int(cfg["spaces"]))]
    rng = np.random.default_rng(5)
    x = rng.uniform(0, world, (len(hs), n)).astype(np.float32)
    z = rng.uniform(0, world, (len(hs), n)).astype(np.float32)
    act = np.zeros(cap, bool)
    act[:n] = True
    r = np.where(act, np.float32(radius), np.float32(0))

    def pad(a):
        o = np.zeros(cap, np.float32)
        o[:n] = a
        return o

    for _ in range(args.ticks):
        x = np.clip(x + rng.uniform(-5, 5, x.shape), 0, world).astype(
            np.float32)
        z = np.clip(z + rng.uniform(-5, 5, z.shape), 0, world).astype(
            np.float32)
        for i, h in enumerate(hs):
            eng.set_subscribed(h, True)
            eng.submit(h, pad(x[i]), pad(z[i]), r, act.copy())
        eng.flush()
    b = hs[0].bucket
    mt, s_n = b._max_triples, len(hs)
    grid = jax.ShapeDtypeStruct((s_n, b.capacity, b.W), jnp.uint32)

    def like(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    comp = A._fused_tri_impl.lower(
        like(b.prev), grid, grid, jax.ShapeDtypeStruct((mt, 3), jnp.int32),
        jax.ShapeDtypeStruct((s_n,), jnp.int32), like(b._dev["x"]),
        like(b._dev["z"]), like(b._dev["r"]), like(b._dev["act"]),
        like(b._dev["sub"]), mt, None).compile()
    text = comp.as_text()
    os.makedirs(args.out_dir, exist_ok=True)
    path = os.path.join(args.out_dir, "aoi_step_tri.hlo.txt")
    with open(path, "w") as f:
        f.write(text)
    print(f"{path}: prev {tuple(b.prev.shape)}, max_triples {mt}, "
          f"{len(text)} characters; {comp.memory_analysis()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
