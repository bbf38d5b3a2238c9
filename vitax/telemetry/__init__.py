"""vitax.telemetry — structured observability for training runs.

Subsystem map:
  flops      analytic model-FLOPs accounting + TPU peak table -> MFU
  sinks      JSONL event log (always-on) + optional TensorBoard mirror
  record     Recorder: versioned per-step records fanned out to sinks
  watchdog   heartbeat hang detector: all-thread stack + memory dumps
  threads    thread-crash excepthook (kind:"thread_crash" events) and
             bounded shutdown joins with leaked-thread warnings

Wired through the training stack by vitax/train/loop.py (Recorder lifecycle,
per-log-step records with the loop thread's timeline, watchdog pets),
vitax/data/loader.py (the `t_got` mark, where the prefetch queue hands a host
batch over) and vitax/config.py (--metrics_dir, --tensorboard,
--peak_tflops, --hang_timeout_s). Everything is host-side: telemetry on or
off, the compiled step program is identical.
"""

from vitax.telemetry.flops import (  # noqa: F401
    PEAK_TFLOPS, detect_peak_tflops, mfu, model_flops_per_image,
    model_flops_per_step)
from vitax.telemetry.record import (  # noqa: F401
    LOOP_MARKS, LOOP_PHASES, REQUIRED_STEP_KEYS, SCHEMA_VERSION, Recorder,
    build_recorder, phase_intervals)
from vitax.telemetry.sinks import (  # noqa: F401
    JsonlSink, TensorBoardSink, make_tensorboard_sink)
from vitax.telemetry.threads import (  # noqa: F401
    install_thread_excepthook, join_or_warn, thread_crash_count)
from vitax.telemetry.watchdog import Watchdog, dump_all_stacks  # noqa: F401
