"""Compile a configuration's full-width prefill and decode-chunk programs
for a described TPU v5e, without the chip, and print their memory.

    JAX_PLATFORMS=cpu python3 bench/aot.py omni6-danube3-4b [--slots 32]

The slots and ``t_max`` of each configuration file were set from these
numbers (argument, output and temporary bytes of one chip), then checked
against ``peak_bytes_in_use`` on the chip.  Nothing runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--slots", type=int, default=0)
    ap.add_argument("--t-max", type=int, default=0)
    ap.add_argument("--prefill", type=int, default=512,
                    help="prefill bucket to compile")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import pool
    from repro.models import build_model
    from repro.serving.engine import Endpoint

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "bench", "configs",
                           f"{args.config}.json")) as f:
        cfg = json.load(f)
    slots = args.slots or cfg["slots"]
    t_max = args.t_max or cfg["t_max"]
    mcfg = pool.model_configs(cfg)[cfg["main_column"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    model = build_model(mcfg)
    page = 16
    n_pages = 1 + slots * (-(-t_max // page))
    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    state = on_chip(jax.eval_shape(
        lambda: model.empty_paged_state(slots, n_pages, page)))
    ep = Endpoint.__new__(Endpoint)        # the chunk body needs only these
    ep.model, ep.cfg, ep.sync_every = model, mcfg, 8
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    chunk = jax.jit(ep._chunk_fn, donate_argnums=(1,)).lower(
        params, state, i32(slots, -(-t_max // page)), i32(slots, 1),
        i32(slots), i32(slots)).compile()
    pre = jax.jit(model.prefill).lower(params, i32(1, args.prefill)).compile()
    for name, c in (("chunk", chunk), (f"prefill[{args.prefill}]", pre)):
        m = c.memory_analysis()
        print(f"{args.config} slots={slots} t_max={t_max} {name}: "
              f"args {m.argument_size_in_bytes / 1e9:.3f} GB, out "
              f"{m.output_size_in_bytes / 1e9:.3f} GB, temp "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, alias "
              f"{m.alias_size_in_bytes / 1e9:.3f} GB")


if __name__ == "__main__":
    main()
