"""Traffic kind `serve_closed`: the server on its defaults, in this process,
under a closed loop of clients in a child process.

Parameters (the traffic mix's file):
  clients            concurrent clients, each posting its next request as
                     soon as the previous reply has arrived
  pool_images        distinct JPEG bodies, encoded once in set-up from the
                     seed (random content, `pool_side_min..max` px, quality
                     `pool_quality`: the recipe of `bench.py:_write_random_jpegs`)
  warm_seconds       closed-loop traffic before the window opens
  rate_span          the steady rate, a per-layer metric, is the median rate
                     at which this many consecutive replies arrive
                     (metrics/serve_steady_images_per_s_chip.py); the judged
                     throughput is every reply over the whole window
                     (metrics/serve_images_per_s_chip.py)
  reference_images   how many of the pool's first images the plain
                     reference answers; every reply to one of them, in set-up
                     and in the window, is held to it

The server is `vitax.serve.server.start_server` on an `InferenceEngine`, as
`python -m vitax.serve` runs it, with every serving knob at its default. The
model is the builder's (`build_model_for`, vitax/programs/builder.py: the
model half of the program's one constructor, which the engine itself takes);
the weights are the trainer's seeded initialisation, made on the device and
handed to the engine the way `from_checkpoint` does after its read: no
checkpoint IO in set-up. The parent holds the chip and runs the server; the
load generator (`_loadgen.py`) never imports JAX.

On a traced run the server also writes its own `serve.jsonl`
(`--metrics_dir`), which the serve per-layer metrics read; with tracing off
it is off, as in a deployment that did not ask for it.
"""

from __future__ import annotations

import io
import json
import math
import os
import pickle
import subprocess
import sys
import threading
import time
import urllib.request

from benchmark import flops as arithmetic   # this kind's FLOPs and parameters
from benchmark import harness
from benchmark.reference import vit as reference

# Served top-k probabilities against the float32 reference's softmax at the
# same class ids, as a difference of log-probabilities. With trunc-normal
# (0.02) weights the logits lie within a few tenths of each other, so a
# comparison of the winning class would flip on rounding; the log-probability
# of a named class does not. The engine computes in bf16 with a float32
# head; measured gap on the chip: 0.009 to 0.029 over 47 runs, at the worst
# of 8 images x 5 classes through 8 blocks of 10B width (PERF.md, PR 22). A
# format with 3 bits of mantissa rounds 32 times coarser: some 0.5.
LOGP_ATOL = 8e-2


def build_config(config_kwargs: dict, traffic: dict, n_devices: int,
                 seed: int):
    """The server's `Config`: the model's shape and the seed. Batching is
    the server's own default, so traffic and chips set nothing."""
    from vitax.config import Config
    return Config(**config_kwargs, seed=seed).validate()


def make_pool(run: harness.Run) -> list:
    """Seeded JPEG bodies; image i depends on (seed, i) alone."""
    import numpy as np
    from PIL import Image
    t = run.traffic
    pool = []
    for i in range(int(t["pool_images"])):
        rng = np.random.default_rng([run.seed, i])
        side = int(rng.integers(t["pool_side_min"], t["pool_side_max"]))
        arr = rng.integers(0, 256, size=(side, side, 3), dtype=np.uint8)
        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, "JPEG", quality=int(t["pool_quality"]))
        pool.append(buf.getvalue())
    return pool


def start_loadgen(run: harness.Run, url: str, pool_path: str):
    spec = {"url": url, "pool_path": pool_path,
            "clients": int(run.traffic["clients"]),
            "warm_seconds": float(run.traffic["warm_seconds"]),
            "seconds": run.seconds,
            "keep_replies_upto": int(run.traffic["reference_images"]),
            "result_path": os.path.join(run.work_dir, "loadgen_result.json")}
    spec_path = os.path.join(run.work_dir, "loadgen_spec.json")
    with open(spec_path, "w", encoding="utf-8") as f:
        json.dump(spec, f)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    child = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "_loadgen.py"), spec_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env)
    return child


def expect_line(child, word: str) -> str:
    line = child.stdout.readline().strip()
    if not line.startswith(word):
        raise RuntimeError(f"load generator said {line!r}, expected {word}")
    return line


def init_params(cfg, mesh, model):
    """The trainer's seeded initialisation, parameters only, made on the
    device in one jitted call, in the layout the engine shards them."""
    import jax
    import jax.numpy as jnp
    from vitax.parallel.sharding import param_specs, shardings_of
    sample_b = mesh.shape["dp"] * mesh.shape["fsdp"]
    sample = jnp.zeros((sample_b, cfg.image_size, cfg.image_size, 3),
                       jnp.float32)

    def init(rng):
        return model.init(rng, sample, True)

    abstract = jax.eval_shape(init, jax.random.key(cfg.seed))
    shardings = shardings_of(mesh, param_specs(abstract, cfg, mesh))
    return jax.jit(init, out_shardings=shardings)(jax.random.key(cfg.seed))


def post(url: str, body: bytes):
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type": "image/jpeg"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return resp.status, json.load(resp)


def logp_gaps(ref_logp, index: int, classes, probs) -> list:
    return [abs(math.log(p) - float(ref_logp[index][c]))
            for c, p in zip(classes, probs)]


def setup(run: harness.Run) -> dict:
    import dataclasses

    import jax
    import numpy as np
    from vitax.parallel.mesh import build_mesh
    from vitax.programs.builder import build_model_for
    from vitax.serve.engine import InferenceEngine
    from vitax.serve.server import decode_image_bytes, start_server

    t0 = time.time()
    pool = make_pool(run)
    pool_path = os.path.join(run.work_dir, "pool.pkl")
    with open(pool_path, "wb") as f:
        pickle.dump(pool, f)
    run.records["pool_s"] = time.time() - t0
    run.records["pool_bytes"] = sum(len(b) for b in pool)

    cfg = build_config(run.config_kwargs, run.traffic, jax.device_count(),
                       run.seed)
    if run.trace_on:
        cfg = dataclasses.replace(
            cfg, metrics_dir=os.path.join(run.work_dir, "serve_metrics"))
    t0 = time.time()
    mesh = build_mesh(cfg)
    model = build_model_for(cfg, mesh)
    params = init_params(cfg, mesh, model)
    engine = InferenceEngine(cfg, mesh, model, params)
    httpd, ctx = start_server(cfg, engine, port=0)
    url = f"http://127.0.0.1:{httpd.server_address[1]}/predict"
    child = start_loadgen(run, url, pool_path)
    live = {"engine": engine, "httpd": httpd, "ctx": ctx, "child": child}
    jax.block_until_ready(params)
    run.records["weights_s"] = time.time() - t0

    warm = engine.warmup()
    run.records["warmup_s"] = {str(b): s for b, s in warm.items()}
    run.program.update(harness.program_facts(
        engine._compiled[engine.buckets[-1]]))
    run.program["buckets"] = list(engine.buckets)
    run.program["param_bytes"] = engine.param_bytes()

    # the reference's answers for the pool's first images, on the pixels
    # the server's own decode and resize produce
    t0 = time.time()
    n_ref = int(run.traffic["reference_images"])
    pixels = np.stack([decode_image_bytes(b, ctx.transform)
                       for b in pool[:n_ref]])
    with jax.default_matmul_precision(reference.PRECISION):
        ref_logp = np.asarray(reference.log_probs(
            engine.params, jax.numpy.asarray(pixels),
            **reference.shape_of(run.config)))
    live["ref_logp"] = ref_logp
    replies = [None] * n_ref

    def one(i):
        replies[i] = post(url, pool[i])

    threads = [threading.Thread(target=one, args=(i,)) for i in range(n_ref)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=150)
    worst = 0.0
    for i, reply in enumerate(replies):
        if not run.check(reply is not None and reply[0] == 200,
                         f"set-up request {i} was not answered 200"):
            continue
        worst = max([worst] + logp_gaps(ref_logp, i, reply[1]["classes"],
                                        reply[1]["probs"]))
    run.checks.update({"setup_logp_gap_max": worst, "logp_atol": LOGP_ATOL,
                       "reference_images": n_ref})
    run.check(worst <= LOGP_ATOL,
              f"served log-probabilities are off the reference by {worst}, "
              f"more than {LOGP_ATOL}")
    run.records["reference_s"] = time.time() - t0
    expect_line(child, "READY")
    return live


def window(run: harness.Run, live: dict, compiles: harness.CompileCounter) -> None:
    child, engine = live["child"], live["engine"]
    with harness.profiler(run):
        compiled_before = compiles.count
        child.stdin.write("GO\n")
        child.stdin.flush()
        expect_line(child, "OPEN")
        with harness.span("window"):
            expect_line(child, "CLOSE")
        compiled_in_window = compiles.count - compiled_before
    expect_line(child, "DONE")
    child.wait(timeout=30)
    with open(os.path.join(run.work_dir, "loadgen_result.json"),
              encoding="utf-8") as f:
        result = json.load(f)

    wrong, worst = 0, 0.0
    for _, index, classes, probs in result["kept"]:
        gap = max(logp_gaps(live["ref_logp"], index, classes, probs))
        worst = max(worst, gap)
        wrong += gap > LOGP_ATOL
    seconds = result["t_close"] - result["t_open"]
    run.records.update({
        "window_open_t": result["t_open"], "window_close_t": result["t_close"],
        "window_s": seconds,
        "attempted": result["attempted"],
        "failed": result["failed"] + wrong,
        "answered": result["attempted"] - result["failed"] - wrong,
        "answered_work": result["answered_work"],
        "arrivals": result["arrivals"],
        "arrival_after_close": result["arrival_after_close"],
        "latency_s": result["latency_s"],
        "latency_quantiles_ms": {
            str(q): 1e3 * harness.percentile(result["latency_s"], q)
            for q in (0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0)} if result["latency_s"] else {},
        "slow_reply_share": (
            sum(x >= 1.0 for x in result["latency_s"])
            / max(len(result["latency_s"]), 1)),
        "statuses": result["statuses"],
        "loadgen_cpu_cores": result["cpu_s"] / seconds,
        "compiles_in_window": compiled_in_window,
        "engine_compile_count": engine.compile_count,
    })
    run.checks.update({"window_logp_gap_max": worst,
                       "window_replies_checked": len(result["kept"])})
    run.check(result["attempted"] > 0, "no reply arrived inside the window")
    run.check(result["still_running"] == 0, "a client thread did not end")
    run.check(run.records["failed"] == 0,
              f"{result['failed']} replies not 200 or malformed, {wrong} off "
              f"the reference (statuses {result['statuses']})")
    run.check(compiled_in_window == 0,
              f"{compiled_in_window} traces or compiles inside the window")
    run.check(engine.compile_count == len(engine.buckets),
              f"engine.compile_count {engine.compile_count} != "
              f"{len(engine.buckets)} buckets after traffic")
    live_peak = harness.live_peak_bytes()
    run.records["live_peak_bytes"] = live_peak
    run.records["memory_peak_bytes"] = max(live_peak or 0,
                                           run.program["step_bytes"])


def finish(run: harness.Run, live: dict) -> None:
    from vitax.serve.server import drain
    child = live.get("child")
    if child is not None and child.poll() is None:
        child.kill()
        child.wait()
    if "httpd" in live:
        run.check(drain(live["httpd"], live["ctx"]), "server did not drain")
    events_path = os.path.join(run.work_dir, "serve_metrics", "serve.jsonl")
    if run.trace_on and os.path.exists(events_path):
        lo = run.records.get("window_open_t", 0.0)
        hi = run.records.get("window_close_t", float("inf"))
        with open(events_path, encoding="utf-8") as f:
            events = [json.loads(ln) for ln in f if ln.strip()]
        run.records["serve_events"] = [
            e for e in events if lo < e.get("time", 0.0) <= hi]
    live.clear()


def lower_described(config_kwargs: dict, traffic: dict, devices):
    """The largest bucket's program lowered for described devices over
    abstract parameters (benchmark/size_cells.py). Nothing runs."""
    from vitax.programs.builder import Geometry, build_program
    from vitax.serve.engine import InferenceEngine
    cfg = build_config(config_kwargs, traffic, len(devices), 0)
    geom = Geometry.assemble(cfg, devices=devices, force_tpu_kernels=True)
    engine = InferenceEngine(cfg, geom.mesh, geom.model,
                             geom.abstract_state.params)
    bucket = engine.buckets[-1]
    lowered = build_program("serve_bucket", geom, bucket=bucket,
                            engine=engine)
    return lowered, f"serve bucket {bucket}"
