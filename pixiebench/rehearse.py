"""Compile each cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 -m pixiebench.rehearse [--workload <cell>]

For every cell (or the one named) this lowers and compiles, at the cell's
real shapes, the graph generator and the server's serving step (the jitted
program ``PixieServer`` dispatches for its default bucket) against one chip
of a described ``v5e:2x2`` topology, and prints each program's
``memory_analysis``.  It finds what the chip's compiler would refuse and
whether the programs fit the chip's memory; it measures no time.  A ranked
configuration's serving step is not compiled (see ``rehearse_cell``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from pixiebench import registry  # noqa: E402


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    names = ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes")
    return {n: int(getattr(m, n)) for n in names if hasattr(m, n)}


def rehearse_cell(cell, config, topo_name: str = "v5e:2x2") -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from pixiebench import graphgen
    from repro.core.graph import CSR, PinBoardGraph
    from repro.core.walk import WalkConfig
    from repro.serving.server import PixieServer

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topo_name)
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    spec = graphgen.spec_from_config(config["graph"])
    out = {"cell": cell["name"]}

    gen = graphgen.device_graph_fn(spec).lower(
        sds((), jnp.int32), sds((), jnp.int32)).compile()
    out["generator"] = _memory(gen)

    n, nb, m, nl = spec.n_pins, spec.n_boards, spec.edge_draws, spec.n_langs
    graph = PinBoardGraph(
        p2b=CSR(sds((n + 1,), jnp.int32), sds((m,), jnp.int32),
                sds((n, nl + 1), jnp.int32)),
        b2p=CSR(sds((nb + 1,), jnp.int32), sds((m,), jnp.int32),
                sds((nb, nl + 1), jnp.int32)),
        n_pins=n, n_boards=nb, max_pin_degree=spec.max_pin_degree,
    )
    if "ranker" in config:
        # the ranked program closes over the ranker's parameters, so it
        # cannot be lowered from shapes alone
        out["serve_step"] = "not compiled: a ranked step needs its parameters"
        return out
    server = PixieServer(graph, WalkConfig(**config["walk"]),
                         n_slots=config["n_slots"])
    b, s = server.batch_size, config["n_slots"]
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), b))
    serve = server._serve.lower(
        graph, sds((b, s), jnp.int32), sds((b, s), jnp.float32),
        sds((b,), jnp.int32), sds(keys.shape, keys.dtype),
        sds((b,), jnp.int32),
    ).compile()
    out["serve_step"] = _memory(serve)
    out["serve_step"]["batch"] = b
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", default=None)
    args = p.parse_args(argv)
    sys.path.insert(0, str(registry.ROOT / "src"))
    bench = registry.load_benchmark()
    for cell in bench["workloads"]:
        if args.workload and cell["name"] != args.workload:
            continue
        config = registry.config(bench, cell["config"])
        print(json.dumps(rehearse_cell(cell, config)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
