#!/usr/bin/env python3
"""Cut a trace recorded on the chip down to a test fixture under 500 KB.

    python3 benchmark/tests/trim_fixture.py <in.xplane.pb[.gz]> <out.xplane.pb.gz> [chips]

A builder's tool, not a test: it needs TensorFlow's `xplane_pb2` to write
the protobuf (reading, in the benchmark and its tests, needs only JAX).
Nothing measured is changed: it keeps the device planes' op lines ("XLA Ops",
"Async XLA Ops") and the harness's `bench/` spans on the host plane, and
drops what the reduction never reads: the other planes (the HLO protos under
`/host:metadata` are most of a trace), the other lines, event statistics
and the statistics of the event metadata (source stacks). The fixtures were
recorded with `--seconds 0.01`: a window of three or four steps. `chips`
keeps only the first so many device planes (each carries its own copy of the
op names: the four-chip fixture keeps chip 0, which is the one the collective
metrics read).
"""

import gzip
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

KEEP_DEVICE_LINES = ("XLA Ops", "Async XLA Ops")


def main(src: str, dst: str, chips: str = "0") -> int:
    opener = gzip.open if src.endswith(".gz") else open
    space = xplane_pb2.XSpace()
    with opener(src, "rb") as f:
        space.ParseFromString(f.read())
    out = xplane_pb2.XSpace()
    keep_chips = int(chips)
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if device and keep_chips and \
                int(plane.name.rsplit(":", 1)[1]) >= keep_chips:
            continue
        if not (device or plane.name in ("/host:CPU", "Task Environment")):
            continue
        new = out.planes.add()
        new.id, new.name = plane.id, plane.name
        if plane.name == "Task Environment":
            new.CopyFrom(plane)
            continue
        used = set()
        for line in plane.lines:
            if device and line.name not in KEEP_DEVICE_LINES:
                continue
            events = [e for e in line.events
                      if device or plane.event_metadata[e.metadata_id]
                      .name.startswith("bench/")]
            if not events:
                continue
            kept = new.lines.add()
            kept.id, kept.name = line.id, line.name
            kept.display_name = line.display_name
            kept.timestamp_ns, kept.duration_ps = (line.timestamp_ns,
                                                   line.duration_ps)
            for e in events:
                ev = kept.events.add()
                ev.metadata_id, ev.offset_ps = e.metadata_id, e.offset_ps
                ev.duration_ps = e.duration_ps
                used.add(e.metadata_id)
        for key in used:
            meta = plane.event_metadata[key]
            new.event_metadata[key].id = meta.id
            new.event_metadata[key].name = meta.name
            new.event_metadata[key].display_name = meta.display_name
    with gzip.open(dst, "wb") as f:
        f.write(out.SerializeToString())
    print(f"{src}: {space.ByteSize()} -> {out.ByteSize()} bytes raw")
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))
