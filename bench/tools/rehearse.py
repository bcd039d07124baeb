"""Compile a configuration's serving programs for a described TPU v5e (no
chip needed) and print what XLA's memory analysis says of each: the
prefill at every prompt bucket given, and the decode step, at the
configuration's width and max_len.

    JAX_PLATFORMS=cpu python3 bench/tools/rehearse.py granite-8b-half 1024
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.launch.steps import make_decode_step, make_prefill_step
    from repro.models import build_model, get_config

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads((BENCH / "configs" / f"{sys.argv[1]}.json")
                      .read_text())
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    m = build_model(get_config(conf["arch"]).replace(**conf["overrides"]))
    W, L = conf["width"], conf["max_len"]

    def sds(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)

    params = jax.tree.map(sds, m.abstract_params())
    cache = jax.tree.map(
        lambda t: sds(t[0]), m.cache_spec(W, L),
        is_leaf=lambda t: isinstance(t, tuple) and len(t) == 2
        and hasattr(t[0], "shape"))
    i32 = jnp.int32
    progs = [(f"prefill{b}", jax.jit(make_prefill_step(m, None, None)),
              (params, cache, {"tokens": jax.ShapeDtypeStruct(
                  (W, int(b)), i32, sharding=one)}))
             for b in sys.argv[2:]]
    progs.append(("decode", jax.jit(make_decode_step(m, None, None)),
                  (params, cache, jax.ShapeDtypeStruct((W, 1), i32,
                                                       sharding=one),
                   jax.ShapeDtypeStruct((W,), i32, sharding=one))))
    gib = 2.0 ** 30
    for name, fn, args in progs:
        t0 = time.perf_counter()
        ma = fn.lower(*args).compile().memory_analysis()
        print(json.dumps({
            "config": conf["name"], "program": name, "width": W,
            "max_len": L, "compile_s": round(time.perf_counter() - t0, 1),
            "argument_gib": ma.argument_size_in_bytes / gib,
            "output_gib": ma.output_size_in_bytes / gib,
            "temp_gib": ma.temp_size_in_bytes / gib}), flush=True)


if __name__ == "__main__":
    main()
