#!/usr/bin/env python3
"""Size a cell without the chip: compile its real program for a described
`v5e:2x2` topology and print the compiler's own memory accounting.

    JAX_PLATFORMS=cpu python3 benchmark/size_cells.py [--workload <cell> ...]
        [--set num_blocks=8] [--set per_chip_batch=256]

A compile that passes is a compile, never a run: it says what fits and which
kernels and collectives the compiler put in, nothing about times. `--set`
overrides one key of the cell's configuration or traffic file for this
compile only (how the batch of the ViT-L/14 cell and the depth of the
four-chip cell were chosen; the sizes chosen are in the files and PERF.md).

Each generator lowers its own program for the described devices
(`lower_described(config_kwargs, traffic, devices)` in
`generators/<kind>.py`), through the program's own constructor. The depth is
printed under the key the configuration's family names for it
(`roles.depth` of `shapes/<family>.json`).
The production Pallas kernels are compiled with real Mosaic lowering
(`VITAX_FORCE_MOSAIC=1`, `force_tpu_kernels`), as `tools/aot_topology.py`
does for `chip_smoke.py`'s programs. Keep the persistent compile cache off:
a described-topology entry cannot be read back without a chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["VITAX_FORCE_MOSAIC"] = "1"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402
from benchmark import manifest as mf  # noqa: E402

TOPOLOGY = "v5e:2x2"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", nargs="*", default=[])
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE")
    args = ap.parse_args()

    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(TOPOLOGY, "tpu")
    man = mf.Manifest()
    hbm = mf.peaks_for("TPU v5e")["hbm_bytes"]
    names = args.workload or [w["name"] for w in man.data["workloads"]]
    for name in names:
        cell = man.cell(name)
        config = man.config(cell["config"])
        traffic = man.traffic(cell["traffic"])
        for item in args.set:
            key, value = item.split("=", 1)
            target = traffic if key in traffic else config
            target[key] = type(target.get(key, 0))(value)
        devices = list(topo.devices)[:cell["chips"]]
        depth_key = man.family(config["family"])["roles"]["depth"]
        t0 = time.time()
        lowered, what = mf.generator(traffic["kind"]).lower_described(
            man.config_kwargs(config), traffic, devices)
        compiled = lowered.compile()
        facts = harness.program_facts(compiled)
        print(json.dumps({
            "workload": name, "program": what, "topology": TOPOLOGY,
            "chips": cell["chips"], "overrides": args.set,
            depth_key: config[depth_key], **facts,
            "step_gb": round(facts["step_bytes"] / 1e9, 3),
            "spare_gb": round((hbm - facts["step_bytes"]) / 1e9, 3),
            "compile_s": round(time.time() - t0, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
