#!/usr/bin/env python3
"""One cell, one process, one last line of JSON.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and the metrics it reports are
all found by name from `BENCHMARK.json` and the files under `benchmark/`
(benchmark/manifest.py); nothing here names one. With `--trace 0` the line
carries the cell's end-to-end metrics, with `--trace 1` its per-layer metrics,
the device's busy seconds and a breakdown of the device trace.

There is no CPU fallback: without a TPU, or with another number of chips than
the cell asks for, the process exits non-zero and prints no result.
`--rehearse` is the only way to run off the chip: the same code at the tiny
shapes of the family's and the traffic file's `rehearse` blocks, pinned to the CPU (virtual
devices for a cell on several chips); its line says `"platform": "cpu"`,
carries every metric's name with the value null, and is no result.
"""

import time

PROCESS_START = time.time()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import manifest as mf  # noqa: E402

NO_CHIP = 3


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="measured window; default: the manifest's run_seconds")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default="",
                    help="another BENCHMARK.json (tests); data files resolve "
                         "against its directory")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on the CPU: control flow only, no result")
    ap.add_argument("--out_dir", default="",
                    help="also write the run's full record (and, traced, the "
                         "gzipped xplane) here")
    return ap.parse_args()


def main() -> int:
    args = parse_args()
    man = mf.Manifest(args.manifest)
    cell = man.cell(args.workload)
    config = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    seconds = args.seconds or float(man.data["run_seconds"])
    if args.rehearse:
        mf.apply_rehearsal(config, traffic, man.family(config["family"]))
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={cell['chips']}").strip()

    # everything the program prints goes to stderr; stdout ends in our line
    out = sys.stdout
    sys.stdout = sys.stderr

    from vitax.platform import setup_compile_cache
    cache_dir = setup_compile_cache()
    import jax
    # the reference check runs dozens of small programs: cache those too, or
    # every run compiles them again in set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if args.rehearse:   # CPU programs have no business in the chip's cache
        jax.config.update("jax_enable_compilation_cache", False)

    from benchmark import harness
    device = harness.device_block()
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"benchmark: no TPU: JAX reports {device}. There is no CPU "
              f"fallback; --rehearse runs the control flow off the chip.",
              file=sys.stderr)
        return NO_CHIP
    if device["count"] != cell["chips"]:
        print(f"benchmark: workload {cell['name']} asks for {cell['chips']} "
              f"chip(s), JAX reports {device['count']}", file=sys.stderr)
        return NO_CHIP

    work_dir = os.path.join(harness.WORK_DIR, cell["name"])
    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    run = harness.Run(cell=cell, config=config,
                      config_kwargs=man.config_kwargs(config), traffic=traffic,
                      seed=args.seed, seconds=seconds,
                      trace_on=bool(args.trace),
                      process_start=PROCESS_START, work_dir=work_dir,
                      device=device)
    if device["platform"] == "tpu":
        run.peaks = mf.peaks_for(device["kind"])
    run.records["compile_cache_dir"] = cache_dir

    gen = mf.generator(traffic["kind"])
    compiles = harness.CompileCounter()
    live = {}
    try:
        live = gen.setup(run)
        gen.window(run, live, compiles)
    finally:
        gen.finish(run, live)
    harness.reduce_trace(run)

    section = "per_layer" if run.trace_on else "end_to_end"
    metrics = {}
    for entry in man.metrics(section, cell["name"]):
        value = mf.metric_reader(entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {
                "value": None if args.rehearse else value,
                "unit": entry["unit"]}

    device["memory_peak_bytes"] = (None if args.rehearse
                                   else run.records.get("memory_peak_bytes"))
    line = {"correct": not run.failures,
            "attempted": run.records.get("attempted", 0),
            "failed": run.records.get("failed", 0),
            "metrics": metrics, "device": device}
    if args.rehearse:
        line["rehearsal"] = True
    if run.trace is not None and not args.rehearse:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.top_ops(10),
                             "idle_gaps": run.trace.idle_gaps(10)}
    if run.failures:
        line["failures"] = run.failures
    line["checks"] = run.checks

    if args.out_dir:
        write_record(args, run, line)
    shutil.rmtree(work_dir, ignore_errors=True)
    out.write(json.dumps(line) + "\n")
    out.flush()
    # each number compared beside its limit, as the last lines of stderr too:
    # where a run is not correct, the end of stderr is what the record keeps
    for what in run.failures:
        print(f"benchmark: NOT CORRECT: {what}", file=sys.stderr)
    print(f"benchmark: correct={not run.failures} checks "
          f"{json.dumps(run.checks)}", file=sys.stderr)
    return 0


def write_record(args, run, line: dict) -> None:
    """The run's whole record, for the builder: too long for the line."""
    os.makedirs(args.out_dir, exist_ok=True)
    stem = os.path.join(
        args.out_dir, f"{run.cell['name']}.trace{int(run.trace_on)}."
                      f"seed{run.seed}")
    small = {k: v for k, v in run.records.items()
             if k != "serve_events"}
    record = {"line": line, "records": small, "program": run.program,
              "n_latencies": len(run.records.get("latency_s", [])),
              # what of the program this process loaded: set-up pays for it
              "program_modules": sorted(m for m in sys.modules
                                        if m.split(".")[0] == "vitax")}
    if run.trace is not None:
        record["category_seconds"] = run.trace.category_seconds()
        record["top_ops_30"] = run.trace.top_ops(30)
        record["devices"] = [
            {"name": d.name, "ops": len(d.ops),
             "busy_s": sum(b - a for a, b in d.busy) / 1e9}
            for d in run.trace.devices]
        record["spans"] = sorted({s[0] for s in run.trace.spans})
        path = run.records.get("xplane_path")
        if path and os.path.getsize(path) < 200e6:
            with open(path, "rb") as src, \
                    gzip.open(stem + ".xplane.pb.gz", "wb") as dst:
                shutil.copyfileobj(src, dst)
    with open(stem + ".json", "w", encoding="utf-8") as f:
        json.dump(record, f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
