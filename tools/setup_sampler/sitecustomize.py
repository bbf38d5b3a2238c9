"""Where a plain `benchmark/run.py` spends its set-up, sampled from inside:

    SAMPLE_OUT=/tmp/sample.txt PYTHONPATH=tools/setup_sampler \\
        python3 benchmark/run.py --workload <cell> --seed <n> --seconds 10 --trace 0

Python imports a `sitecustomize` it finds on its path at start-up; this one
does nothing unless `SAMPLE_OUT` is set and the process runs
`benchmark/run.py`. Then a daemon thread reads the main thread's stack every
20 ms and, at exit, writes the inclusive seconds a (file, function): over the
whole process, then for the four lines of the generator's `setup` that took
longest (`compiled = step.lower(...).compile()` is where a cached run's
`compile_or_cache_s` goes), then the leaves. The command, its arguments and
its call stack are the plain run's own, which a wrapper's are not: the Mosaic
payloads embed the stack, and PR 43's +7.5 s of a cached `compile_or_cache_s`
(a `jax.jit` that missed its trace cache once a layer run, on the chip only)
showed under this thread and not under a wrapper that timed JAX's stages. The
thread takes the interpreter lock to read a stack, so a phase that is all
Python reads short (12 s of 21): compare two trees, not a tree with a clock.
"""

import atexit
import collections
import os
import sys
import threading

_OUT = os.environ.get("SAMPLE_OUT")
PERIOD_S = 0.02


class Sampler:
    """The thread, what it has counted, and its end (`close`, at exit)."""

    def __init__(self, out: str):
        self.out = out
        self.counts = collections.Counter()
        self.by_line = collections.defaultdict(collections.Counter)
        self.leaves = collections.Counter()
        self.main_id = threading.main_thread().ident
        self.stop = threading.Event()
        self.lock = threading.Lock()    # the counters: the thread's, and dump's
        self.thread = threading.Thread(target=self.sample, daemon=True,
                                       name="setup-sampler")

    def start(self) -> None:
        self.thread.start()
        atexit.register(self.close)

    def close(self) -> None:
        self.stop.set()
        self.thread.join(timeout=1.0)
        self.dump()

    def sample(self) -> None:
        while not self.stop.wait(PERIOD_S):
            frame = sys._current_frames().get(self.main_id)
            seen, line, leaf = set(), None, None
            while frame is not None:
                code = frame.f_code
                key = (code.co_filename.split("site-packages/")[-1],
                       code.co_name)
                leaf = leaf or key
                seen.add(key)
                if code.co_name == "setup" and os.path.join(
                        "benchmark", "generators") in code.co_filename:
                    line = frame.f_lineno
                frame = frame.f_back
            with self.lock:
                self.leaves[leaf] += 1
                self.counts.update(seen)
                if line is not None:
                    self.by_line[line].update(seen)

    def dump(self) -> None:
        with self.lock, open(self.out, "w") as f:
            f.write(f"{sum(self.leaves.values())} samples, one a "
                    f"{PERIOD_S} s\n")
            for (name, fn), n in self.counts.most_common(140):
                f.write(f"{n * PERIOD_S:8.2f}s incl  {name}:{fn}\n")
            longest = sorted(self.by_line.items(),
                             key=lambda kv: -max(kv[1].values()))[:4]
            for line, inside in longest:
                f.write(f"SETUP LINE {line}: "
                        f"{max(inside.values()) * PERIOD_S:.2f}s\n")
                for (name, fn), n in inside.most_common(70):
                    f.write(f"{n * PERIOD_S:8.2f}s incl  {name}:{fn}\n")
            f.write("LEAVES\n")
            for (name, fn), n in self.leaves.most_common(40):
                f.write(f"{n * PERIOD_S:8.2f}s self  {name}:{fn}\n")


if _OUT and any(a.endswith(os.path.join("benchmark", "run.py"))
                for a in sys.argv):
    Sampler(_OUT).start()
