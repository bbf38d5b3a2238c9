#!/usr/bin/env python3
"""Whether two trees build the same programs, off the chip: each cell's
program lowered for a described `v5e:2x2`, and two such dumps compared
payload by payload.

    JAX_PLATFORMS=cpu python3 benchmark/lowered.py dump <dir>     (in each tree)
    python3 benchmark/lowered.py compare <dir_a> <dir_b>

One sha256 of `lowered.as_text()` is too blunt: a Mosaic kernel's payload
embeds the call stack it was traced under, file names and line numbers, so
moving the line of a generator's own `step.lower(...)` call changes the text
(and the compile cache's key) and nothing of the program. Unpack both trees
AT THE SAME PATH in turn and dump both with this script (copy it into a tree
that lacks it): the stack holds the dumping script's own frames too, and
file names have to agree. `compare` holds every
line equal outside the payloads, decodes the payloads, and prints per cell:

    identical      the same text
    payload_only   the texts differ inside Mosaic payloads alone: how many
                   payloads, how many bytes, and (where the payloads have
                   the same length) the pairs of values at those bytes, read
                   as MLIR bytecode's two-byte varints: a line number that
                   moved shows as one pair, (274, 260)
    differs        a line differs outside a payload: another program

and exits 1 if any cell `differs`.
"""

from __future__ import annotations

import base64
import os
import re
import sys

TOPOLOGY = "v5e:2x2"
PAYLOAD = re.compile(r'(?<=body\\22: \\22)[A-Za-z0-9+/=]+')


def dump(out_dir: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["VITAX_FORCE_MOSAIC"] = "1"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    from benchmark import manifest as mf
    topo = topologies.get_topology_desc(TOPOLOGY, "tpu")
    man = mf.Manifest()
    os.makedirs(out_dir, exist_ok=True)
    for cell in man.data["workloads"]:
        config = man.config(cell["config"])
        traffic = man.traffic(cell["traffic"])
        lowered, _ = mf.generator(traffic["kind"]).lower_described(
            man.config_kwargs(config), traffic,
            list(topo.devices)[:cell["chips"]])
        with open(os.path.join(out_dir, cell["name"] + ".txt"), "w",
                  encoding="utf-8") as f:
            f.write(lowered.as_text())


def _decode(blob: str) -> bytes:
    return base64.b64decode(blob + "=" * (-len(blob) % 4))


def compare_texts(a: str, b: str) -> dict:
    """{verdict, payloads, bytes, values} for two lowered texts."""
    if a == b:
        return {"verdict": "identical", "payloads": 0, "bytes": 0,
                "values": []}
    lines_a, lines_b = a.splitlines(), b.splitlines()
    out = {"verdict": "payload_only", "payloads": 0, "bytes": 0,
           "values": set()}
    if len(lines_a) != len(lines_b):
        return {**out, "verdict": "differs", "values": []}
    for x, y in zip(lines_a, lines_b):
        if x == y:
            continue
        if PAYLOAD.sub("@", x) != PAYLOAD.sub("@", y):
            return {**out, "verdict": "differs", "values": []}
        for u, v in zip(PAYLOAD.findall(x), PAYLOAD.findall(y)):
            if u == v:
                continue
            du, dv = _decode(u), _decode(v)
            out["payloads"] += 1
            if len(du) != len(dv):
                out["bytes"] += abs(len(du) - len(dv))
                continue
            at = [i for i in range(len(du)) if du[i] != dv[i]]
            out["bytes"] += len(at)
            out["values"] |= {      # read where a run of differing bytes begins
                (int.from_bytes(du[i:i + 2], "little") >> 2,
                 int.from_bytes(dv[i:i + 2], "little") >> 2)
                for i in at if i - 1 not in at}
    out["values"] = sorted(out["values"])
    return out


def compare(dir_a: str, dir_b: str) -> int:
    worst = 0
    for name in sorted(os.listdir(dir_a)):
        with open(os.path.join(dir_a, name), encoding="utf-8") as f:
            a = f.read()
        with open(os.path.join(dir_b, name), encoding="utf-8") as f:
            b = f.read()
        found = compare_texts(a, b)
        print(name, found)
        worst |= found["verdict"] == "differs"
    return worst


if __name__ == "__main__":
    if len(sys.argv) == 3 and sys.argv[1] == "dump":
        dump(sys.argv[2])
    elif len(sys.argv) == 4 and sys.argv[1] == "compare":
        sys.exit(compare(sys.argv[2], sys.argv[3]))
    else:
        sys.exit(__doc__)
