"""What a cell's step costs to trace and lower, off the chip: the part of a
run's `compile_or_cache_s` that a full compile cache does not save. A cached
run still traces the step in Python and lowers it, every Pallas body at every
site it is called from, before a cache key exists.

    JAX_PLATFORMS=cpu python3 tools/lowering_seconds.py [--workload <cell> ...]

Each cell's step is lowered through its generator's `lower_described` for a
described `v5e:2x2` (benchmark/lowered.py's recipe; nothing is compiled,
nothing runs), twice in one process: the first call pays the imports and every
trace, the second finds what `jax.jit` keeps (a jit-wrapped kernel's jaxpr)
and pays the rest again. A line a cell: the seconds of both, the
`tpu_custom_call`s in the text and the `func.call`s (a body lowered once and
called from many sites shows as calls, not as custom calls). The seconds are
this host's Python, not a device's: run it on the host whose set-up is in
question (`chiprun -- env JAX_PLATFORMS=cpu python3 tools/lowering_seconds.py`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT = ["ling3_flash_vl_ep64tp2_train_packed4k"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", nargs="*", default=DEFAULT)
    args = ap.parse_args()

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["VITAX_FORCE_MOSAIC"] = "1"
    t0 = time.perf_counter()
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    from benchmark import manifest as mf
    from benchmark.lowered import TOPOLOGY
    topo = topologies.get_topology_desc(TOPOLOGY, "tpu")
    man = mf.Manifest()
    print(json.dumps({"import_and_topology_s":
                      round(time.perf_counter() - t0, 2)}), flush=True)
    for name in args.workload:
        cell = man.cell(name)
        traffic = man.traffic(cell["traffic"])
        kwargs = man.config_kwargs(man.config(cell["config"]))
        devices = list(topo.devices)[:cell["chips"]]
        line = {"workload": name}
        for tag in ("first_s", "again_s"):
            t0 = time.perf_counter()
            lowered, _ = mf.generator(traffic["kind"]).lower_described(
                kwargs, traffic, devices)
            line[tag] = round(time.perf_counter() - t0, 2)
        text = lowered.as_text()
        line["tpu_custom_calls"] = text.count("@tpu_custom_call")
        line["func_calls"] = text.count("call @")
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
