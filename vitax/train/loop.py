"""Training orchestration: the reference's train() / eval_on_val() / run_logging()
(reference run_vit_training.py:216-318; SURVEY.md sections 3.1-3.4), TPU-native.

One process per host drives all local devices; the hot loop dispatches one
compiled train_step per iteration. Device->host syncs happen only at log steps
(the role of the reference's xm.add_step_closure throttling, run_vit_training.py:289):
JAX's async dispatch returns futures, so we hold the metrics of the most recent
step and fetch them when logging.

The loop thread's timeline. Every iteration stamps five marks on `time.time()`
(the clock of a `--profile_dir` device trace and of the server's marks), and a
phase lasts from its mark to the next one:

  t_next      the loop asks the loader for a batch    wait: blocked on the
              (the end of the iteration before)       prefetch queue
  t_got       the loader's queue handed a host batch  put: the host-to-device
              over (stamped by the loader; one        hand-off, up to the batch
              without a queue gives t_next)           reaching the loop
  t_batch     the loop has the device batch           dispatch: the train_step
                                                      call
  t_dispatch  train_step returned                     fence: blocked on the loss
                                                      (log steps, the step that
                                                      arms the watchdog or
                                                      closes a trace; else zero)
  t_fence     the loss arrived (t_dispatch where no   host: all the rest, up to
              fence was taken)                        the next t_next

so the phases of consecutive iterations tile the thread's time with no hole:
fault hook, watchdog, logging, the step record's fetches and write, snapshot
submit and control poll are `host`, and so is what an epoch's end does (its
fence, save and evaluation) before the next epoch's first t_next. A step
record carries the rows since the record before as `loop_marks`
(vitax/telemetry/record.py: LOOP_MARKS), its own last; `data_wait_s` is their
mean `wait`. `wait` is queue time, not starvation by itself: the loop runs up
to a log interval of steps ahead of the device, and a loader slower than the
dispatch but faster than the device fills `wait` with time the device never
sees (`fence` shrinks by as much); a run is input-bound where `fence` has gone
to 0. Cost: five clock reads and a list append a step, taken always,
written where a recorder is.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import pprint
import time
from typing import Optional

import jax
import jax.numpy as jnp

from vitax import distributed, faults, platform
from vitax.checkpoint import (restore_state, restore_state_with_fallback,
                              save_state)
from vitax.config import Config
from vitax.data import build_datasets
from vitax.models import count_params
from vitax.parallel.mesh import build_mesh
from vitax.train.control import ArbiterReporter, ControlPlane
from vitax.programs.builder import Geometry, build_program
from vitax.programs.kernels import Kernels, kernel_lines
from vitax.train.state import TrainState
from vitax.telemetry import (Watchdog, build_recorder,
                             install_thread_excepthook)
from vitax.telemetry.watchdog import EXIT_HANG
from vitax.utils.logging import master_print, memory_summary
from vitax.utils.metrics import SmoothedValue

# Multi-host failure-signal agreement (SIGTERM preemption, watchdog
# escalation, fault/peer-loss bits) is the control plane's job now:
# vitax/train/control.py folds them into one packed word agreed across hosts
# every --control_sync_steps steps (plus each epoch boundary) — the same
# single tiny collective the preemption-only flag sync used to cost.


def _sharded_param_count(state: TrainState) -> int:
    """Per-device (sharded) parameter count — the reference prints this as
    'per-TPU (sharded) parameter num' (run_vit_training.py:234)."""
    total = 0
    for leaf in jax.tree.leaves(state.params):
        shard = leaf.addressable_shards[0]
        # host-side: shapes are static python tuples; jnp.prod here would
        # dispatch (and sync on) one tiny device program per parameter leaf
        total += math.prod(shard.data.shape)
    return total


ONE_LAYER = "one layer (merged with the first when compiled)"


def _attention_remat_note(cfg: Config, model, mesh) -> str:
    """The `attention core:` line's second half: whether a rematted block
    keeps the forward kernel's o and lse or runs the kernel again in its
    backward, and why (vitax/models/vit.py: keeps_attention_residuals;
    vitax/models/decoder.py: run_remat_policy). The pipeline body and the
    group forwards (vitax/train/step.py: _forward_fn) checkpoint blocks
    themselves and always recompute."""
    from vitax.models.vit import (ATTN_KEEP_MIN_SPAN, attention_span,
                                  keeps_attention_residuals)
    from vitax.parallel.sharding import gather_overlap_active
    if model.attention_impl is None or not cfg.grad_ckpt:
        return ""
    if cfg.decoder:   # by each run's kind (its span) and length
        from vitax.models.decoder import (
            NO_ATTENTION, _decoder_attention_saveable, run_remat_policy)
        said = dict.fromkeys(
            (kind, length > 1,
             run_remat_policy(model, kind, length) is _decoder_attention_saveable)
            for (kind, _, _), length in model.runs()
            if kind not in NO_ATTENTION)
        return "; remat " + ", ".join(
            f"{'keeps o and lse' if kept else 'runs the forward again'} in "
            f"{kind} runs of {'several layers' if several else ONE_LAYER}"
            f" (span {model.span(kind)})" for kind, several, kept in said)
    span = attention_span(model)
    if (mesh.shape.get("pp", 1) > 1 or gather_overlap_active(cfg, mesh)
            or cfg.remat_window > 1):
        why = "this forward checkpoints groups of blocks itself"
    elif keeps_attention_residuals(model):
        return (f"; remat keeps its o and lse (span {span} >= "
                f"{ATTN_KEEP_MIN_SPAN} tokens)")
    elif cfg.remat_policy != "none_saveable":
        return f"; remat policy {cfg.remat_policy}"
    elif span >= ATTN_KEEP_MIN_SPAN:
        why = "sequence-parallel: one set of o and lse a ring step"
    else:
        why = f"span {span} < {ATTN_KEEP_MIN_SPAN} tokens"
    return f"; remat runs its forward again ({why})"


# a decoder step's own counters (vitax/train/step.py: decoder_counts), as its
# step record carries them
DECODER_COUNTERS = ("tokens", "padding_tokens", "images", "targets",
                    "causal_pairs", "window_pairs", "causal_computed_pairs",
                    "window_computed_pairs",
                    "expert_slots_here", "expert_rows_computed",
                    "ssd_pairs", "ssd_live_chunks",     # mamba layers only
                    # ... with kda layers, with a router that has groups:
                    "kda_pairs", "kda_live_chunks",
                    "tokens_choosing_held_group",
                    # ... a router bias the trainer balances; ReGLU experts:
                    "route_load_max_over_mean", "expert_hidden_live")
PACKED_COUNTERS = ("tokens", "padding_tokens", "images", "token_pairs",
                   "computed_pairs")


def train(cfg: Config) -> TrainState:
    # persistent XLA compilation cache, placed by JAX_COMPILATION_CACHE_DIR
    # or at the fixed in-checkout default: restarts (launcher --restart,
    # preemption resume, --resume_epoch) skip the recompile of the step
    # program — minutes at 10B scale
    platform.setup_compile_cache()
    distributed.maybe_initialize()
    if cfg.debug_nans:
        jax.config.update("jax_debug_nans", True)

    master_print(f"\n=== cfg ===\n{pprint.pformat(cfg)}\n")
    # deterministic fault injection (--fault_plan / VITAX_FAULT_PLAN): armed
    # before any hook site can fire, re-armed identically on every
    # (supervised) restart; a no-plan run pays one `is None` check per hook
    fault_plan = faults.install_from_config(cfg)
    if fault_plan is not None:
        master_print(f"fault injection ARMED (drill): {fault_plan.describe()}")
    mesh = build_mesh(cfg)
    master_print(f"mesh: {dict(mesh.shape)} over {jax.device_count()} devices "
                 f"({jax.process_count()} host(s))")

    # --- datasets (reference :223-225) ---
    train_ds, train_loader, _, val_loader = build_datasets(cfg, mesh)
    distributed.barrier("loaded dataset")
    master_print(f"\n=== dataset ===\n{pprint.pformat(train_ds)}\n")

    # --- model + optimizer, born sharded (reference :228-242) ---
    auto_resume = cfg.resume_epoch < 0
    # zero-stall snapshot pipeline + peer replication (vitax/checkpoint/
    # snapshot.py, peer.py): built BEFORE resume so the peer store can be
    # negotiated as the restore source — restoring a lost host's shard from
    # its surviving ring buddy reads nothing from shared storage
    snap_pipe = replicator = peer_plan = None
    orbax_found = 0
    deferred_events = []  # (kind, payload) raised before the recorder exists
    if cfg.zero_stall_ckpt or cfg.replicate_steps > 0:
        from vitax.checkpoint.snapshot import SnapshotPipeline
        snap_pipe = SnapshotPipeline()
        master_print("zero-stall checkpointing: staging on the loop thread, "
                     "serialize + write on a background worker")
    if cfg.replicate_steps > 0:
        from vitax.checkpoint import peer as peer_mod
        from vitax.train.control import coordination_client
        store = peer_mod.PeerStore(peer_mod.resolve_peer_dir(cfg))
        replicator = peer_mod.PeerReplicator(
            store, process_index=jax.process_index(),
            process_count=jax.process_count(), client=coordination_client())
        replicator.start_receiver()
        master_print(f"peer replication: every {cfg.replicate_steps} steps "
                     f"-> buddy host {replicator.buddy} (local store "
                     f"{store.root}, guarding host {replicator.guard})")
    if auto_resume:  # auto-resume: latest COMMITTED checkpoint, if any
        from vitax.checkpoint.orbax_io import latest_epoch
        # process 0 picks, everyone adopts: a non-atomic shared-store view
        # (e.g. GCS fuse) must not let hosts disagree on the resume epoch;
        # latest_epoch validates the Orbax commit marker, so a torn dir a
        # crash left mid-write is never selected
        found = distributed.broadcast_from_process0(latest_epoch(cfg.ckpt_dir) or 0)
        cfg = dataclasses.replace(cfg, resume_epoch=found)
        master_print(f"auto-resume: {'epoch ' + str(found) if found else 'no checkpoint found, fresh start'}")
        orbax_found = found
        if replicator is not None:
            # restore-from-peers preferred: the newest complete peer version
            # that beats the Orbax frontier wins (agreed by ALL hosts via
            # the BIT_PEER_RESTORE fold; survivors serve the lost host's
            # shard over the KV seam during the negotiation)
            from vitax.checkpoint.orbax_io import load_resume_step
            frontier = ((0, 0) if not found else peer_mod.progress_key(
                found, load_resume_step(cfg.ckpt_dir, found) or 0))
            peer_plan = peer_mod.negotiate_restore(
                replicator.store, process_index=jax.process_index(),
                process_count=jax.process_count(),
                client=coordination_client(), orbax_frontier=frontier,
                on_event=lambda kind, payload:
                    deferred_events.append((kind, payload)))
            if peer_plan is not None:
                cfg = dataclasses.replace(cfg, resume_epoch=peer_plan.epoch)
                master_print(
                    f"peer restore agreed: version {list(peer_plan.version)}"
                    f" is at least as fresh as the Orbax frontier "
                    f"{list(frontier)} — restoring from peer shards, not "
                    f"shared storage")
    # step-granular resume: a mid-epoch (preemption) checkpoint carries the
    # completed step count in a sidecar — continue INSIDE that epoch instead
    # of skipping its remainder (improves on the reference's epoch-granular
    # --resume_epoch contract, run_vit_training.py:246-248). Elastic: a
    # checkpoint written under a DIFFERENT process count resumes too
    # (_elastic_resume — Orbax reshards the state; the step either carries
    # over exactly or is epoch-rounded when a stream cursor pins topology)
    resume_step = 0
    topology_change = None  # (from, to) process counts when they differ
    resume_rounded = False  # cursor invalidated -> re-enter the SAME epoch
    if peer_plan is not None:
        # the peer meta is sidecar-shaped: the same elastic planner decides
        # the re-entry step (every host reads identical agreed-version meta)
        from vitax.train.control import elastic_resume_plan
        plan = elastic_resume_plan(peer_plan.meta, jax.process_count())
        resume_step = plan.resume_step
        topology_change = ((plan.from_processes, jax.process_count())
                           if plan.topology_changed else None)
        resume_rounded = plan.epoch_rounded
    elif cfg.resume_epoch > 0:
        resume_step, topology_change, resume_rounded = _elastic_resume(
            cfg, cfg.resume_epoch)
    # re-derived from the LIVE loader each run (not a checkpointed value):
    # an elastic restart under a different topology gets the cadence its
    # CURRENT shard assignment supports (the stream sampler's steps_per_epoch
    # depends on process count; the index-sampled loaders' does not)
    steps_per_epoch = (cfg.steps_per_epoch
                       or getattr(train_loader, "steps_per_epoch", 0)
                       or (len(train_ds) // cfg.batch_size))
    max_iteration = steps_per_epoch * cfg.num_epochs
    # the one assembly (vitax/programs/builder.py): attention core, model
    # with its activation anchors, the scenario's optimizer (registry.py:
    # --task train/distill get the reference AdamW chain verbatim, finetune
    # appends the masked backbone-lr scale, probe masks the backbone frozen
    # with head-only moments) and the state born sharded. On resume only
    # the ABSTRACT state is built (no device materialization — the
    # checkpoint supplies the values; reference :246-248) and restored into.
    geom = Geometry.assemble(cfg, max_iteration,
                             materialize=cfg.resume_epoch <= 0)
    model, schedule = geom.model, geom.schedule
    lines = kernel_lines(cfg, model.kernels if cfg.decoder else Kernels(
        attention=model.attention_impl))
    lines[0] += _attention_remat_note(cfg, model, geom.mesh)
    for line in lines:
        master_print(line)
    # the loop owns the state: a restore or a warm start replaces it, every
    # step donates it
    state, geom.state = geom.state, None
    restore_info = None  # {"path": "peer"|"orbax", "epoch": N} for telemetry
    from vitax.checkpoint.orbax_io import restore_read_count
    reads_before_restore = restore_read_count()  # delta = THIS run's reads
    if cfg.resume_epoch > 0:
        if peer_plan is not None:
            # peer shards first; a checksum/coverage failure falls back
            # LOUDLY to the last committed Orbax epoch (restore_info tells
            # us which path actually won)
            state, restore_info = peer_mod.restore_state_preferring_peers(
                replicator.store, peer_plan, cfg.ckpt_dir, orbax_found,
                state, on_event=lambda kind, payload:
                    deferred_events.append((kind, payload)))
            if restore_info["path"] == "orbax":
                if restore_info["epoch"] != cfg.resume_epoch:
                    cfg = dataclasses.replace(
                        cfg, resume_epoch=restore_info["epoch"])
                resume_step, topology_change, resume_rounded = (
                    _elastic_resume(cfg, cfg.resume_epoch))
        elif auto_resume:
            # an auto-resume must survive one bad checkpoint: fall back to
            # the previous committed epoch (loudly) instead of wedging
            state, restored = restore_state_with_fallback(
                cfg.ckpt_dir, cfg.resume_epoch, state)
            if restored != cfg.resume_epoch:
                cfg = dataclasses.replace(cfg, resume_epoch=restored)
                resume_step, topology_change, resume_rounded = (
                    _elastic_resume(cfg, restored))
            restore_info = {"path": "orbax", "epoch": cfg.resume_epoch}
        else:  # an explicit --resume_epoch N must mean N — fail hard
            state = restore_state(cfg.ckpt_dir, cfg.resume_epoch, state)
            restore_info = {"path": "orbax", "epoch": cfg.resume_epoch}
    if cfg.init_npz and cfg.resume_epoch <= 0:
        # finetune/probe warm start: overwrite the fresh sharded init from
        # the consolidated export (head re-init per --reinit_head / shape);
        # an Orbax resume above takes precedence — the checkpoint already
        # embodies the warm-started run
        from vitax.programs.workloads import warm_start_from_npz
        state, ft_info = warm_start_from_npz(cfg, state, mesh)
        deferred_events.append(("finetune", ft_info))
    distributed.barrier("loaded model")
    master_print(f"\n=== model ===\n{model}\n")
    master_print(f"global parameter num: {count_params(state.params)}")
    master_print(f"per-device (sharded) parameter num: {_sharded_param_count(state)}")
    from vitax.train.state import ADAMW_HPARAMS
    master_print(  # optimizer dump at startup (reference run_vit_training.py:242)
        f"\n=== optimizer ===\nAdamW(lr=warmup_cosine(base={cfg.lr}, "
        f"warmup={cfg.warmup_steps}, max_iteration={max_iteration}), "
        f"betas=({ADAMW_HPARAMS['b1']}, {ADAMW_HPARAMS['b2']}), "
        f"eps={ADAMW_HPARAMS['eps']}, weight_decay={cfg.weight_decay}, "
        f"clip_grad_norm={cfg.clip_grad_norm})\n")
    distributed.barrier("loaded optimizer")

    if cfg.grad_accum_steps > 1:
        # step-count/logging semantics are UNCHANGED: the scan over K
        # microbatches lives inside the compiled step, so each loader batch
        # is still exactly one optimizer step / one log line / one lr tick.
        master_print(
            f"grad accumulation: {cfg.grad_accum_steps} microbatches of "
            f"{cfg.batch_size // cfg.grad_accum_steps} inside the jitted "
            f"step (one optimizer step per loader batch)")
    # every jitted program the loop runs comes from build_program on that
    # one geometry
    train_step = build_program(geom.scenario.step_program, geom)
    eval_step = build_program("eval", geom)

    smoothed_loss = SmoothedValue(window_size=5)
    smoothed_time = SmoothedValue(window_size=5)
    from vitax.train import preempt
    preempt.install()  # SIGTERM -> committed save -> clean exit

    # --- telemetry (vitax/telemetry/): all host-side — the compiled step
    # program and its dispatch cadence are identical with telemetry off ---
    recorder = build_recorder(cfg, jax.device_count(),
                              platform.device_kind(),
                              rank=jax.process_index())
    # uncaught exceptions in ANY background thread (loader producers,
    # watchdog, heartbeats, snapshot writer, peer receiver) become
    # rank-tagged stderr tracebacks + kind:"thread_crash" events instead
    # of silent thread deaths (recorder=None still tags stderr)
    install_thread_excepthook(recorder, rank=jax.process_index())
    if recorder is not None:
        master_print(f"telemetry: JSONL step records -> {cfg.metrics_dir} ("
                     + (f"MFU vs {recorder.peak_tflops:.0f} TF/s/chip peak"
                        if recorder.peak_tflops is not None
                        else "no MFU on the host CPU")
                     + (", tensorboard mirror on" if cfg.tensorboard else "")
                     + ")")
        recorder.event("run_start", device_kind=recorder.device_kind,
                       n_devices=recorder.n_devices,
                       peak_tflops=recorder.peak_tflops,
                       flops_per_step=recorder.flops_per_step,
                       batch_size=cfg.batch_size)
        if fault_plan is not None:  # fired faults become kind:"fault" events
            faults.set_reporter(
                lambda payload: recorder.event("fault", **payload))
        for kind, payload in deferred_events:
            recorder.event(kind, **payload)  # pre-recorder restore events
        if restore_info is not None:
            # which restore path actually won, plus the shared-storage read
            # counter — the peer-restore drill asserts path=="peer" with
            # orbax_reads == 0 (zero checkpoint state read from storage)
            recorder.event("restore", path=restore_info["path"],
                           epoch=int(restore_info["epoch"]),
                           resume_step=int(resume_step),
                           orbax_reads=restore_read_count()
                           - reads_before_restore)
    if replicator is not None and recorder is not None:
        replicator.on_event = (lambda kind, payload:
                               recorder.event(kind, **payload))
    watchdog = None
    if cfg.hang_timeout_s > 0:
        on_fire = ((lambda payload: recorder.event("hang", **payload))
                   if recorder is not None else None)
        on_escalate = ((lambda payload: recorder.event("hang_escalation",
                                                       **payload))
                       if recorder is not None else None)
        # built here, ARMED at the first dispatch return (see the step loop):
        # the first step blocks on XLA compilation — minutes at 10B scale —
        # and a watchdog ticking through it would escalate on a healthy run
        watchdog = Watchdog(cfg.hang_timeout_s, on_fire=on_fire,
                            rank=jax.process_index(),
                            action=cfg.hang_action,
                            on_escalate=on_escalate)
        master_print(
            f"watchdog: stack+memory dump after {cfg.hang_timeout_s:.0f}s "
            f"without a completed step (armed after the compile step)"
            + (f", then emergency checkpoint + exit {EXIT_HANG}"
               if cfg.hang_action == "checkpoint_exit" else ""))

    # --- coordinated failure control plane (vitax/train/control.py): every
    # host-local signal — preemption, escalation, fault, peer loss — folds
    # into one packed word agreed across hosts, so all hosts take the same
    # action at the same step. Host-side only, like telemetry and faults:
    # the compiled step program is identical with the plane on or off. ---
    control = ControlPlane(
        sync_steps=cfg.control_sync_steps, watchdog=watchdog,
        on_event=((lambda payload: recorder.event("control", **payload))
                  if recorder is not None else None))
    if watchdog is not None:
        # last words for the hard-deadline exit: a flushed telemetry event
        # plus the fault bit published through the coordination service, so
        # peers learn the cause instead of just losing a heartbeat
        def _hard_exit_last_words(payload, _recorder=recorder,
                                  _control=control):
            if _recorder is not None:
                _recorder.event("hang_hard_exit", **payload)
            _control.publish_fault("hang_hard_exit")
        watchdog.on_hard_exit = _hard_exit_last_words
    if topology_change is not None and recorder is not None:
        # the RESUME action, not the observation: the supervisor already
        # records `topology_change` when it spots the mismatch — this one
        # says the loop actually re-derived its schedule under the new
        # process count (distinct events, or the report double-counts)
        recorder.event("control", event="elastic_resume",
                       from_processes=topology_change[0],
                       to_processes=topology_change[1],
                       epoch=cfg.resume_epoch, resume_step=resume_step,
                       epoch_rounded=resume_rounded)
    if cfg.peer_heartbeat_s > 0:
        grace_s = cfg.peer_grace_s or 10.0 * cfg.peer_heartbeat_s
        if control.start_liveness(cfg.peer_heartbeat_s, grace_s):
            master_print(
                f"peer liveness: heartbeats every {cfg.peer_heartbeat_s:g}s "
                f"through the coordination service; a peer silent for "
                f"{grace_s:g}s is declared lost and survivors exit "
                f"{EXIT_HANG} within the deadline instead of blocking in "
                f"collectives")

    arbiter_reporter = None
    if cfg.arbiter_url and jax.process_index() == 0:
        # chip-arbiter heartbeat (vitax/arbiter/): rank 0 posts the latest
        # committed step so borrow policy sees live progress. Host-side
        # thread only — the compiled step program is unchanged.
        arbiter_reporter = ArbiterReporter(
            cfg.arbiter_url, process_count=jax.process_count())
        arbiter_reporter.start()
        master_print(f"arbiter telemetry: posting step heartbeats to "
                     f"{cfg.arbiter_url}")

    control.warmup()  # compile the agreement fold outside any hang deadline
    distributed.barrier("training begins")
    master_print("training begins (the first few iterations are very slow due to compilation)")

    prof = {"on": False}  # shared so the finally can close a mid-flight trace
    try:
        state = _run_epochs(
            cfg, state, train_step, train_loader, val_loader, eval_step,
            schedule, smoothed_loss, smoothed_time, prof,
            resume_step=resume_step, resume_rounded=resume_rounded,
            recorder=recorder, watchdog=watchdog, control=control,
            snap_pipe=snap_pipe, replicator=replicator,
            arbiter_reporter=arbiter_reporter)
    except Exception as e:  # noqa: BLE001 — classify, then exit coordinated or re-raise
        # A dead peer shows up two ways: ICI collectives BLOCK on it (the
        # liveness deadline timer bounds that), host-plane transports like
        # Gloo surface it as a runtime ERROR instead. Ask the liveness
        # monitor which this is: a lost-peer verdict means the error is the
        # death itself — exit EXIT_HANG like every other coordinated
        # escalation (the last committed checkpoint stands; no joint save is
        # possible over a dead peer). Peers all beating == a genuine bug:
        # re-raise it untouched.
        lost = control.peer_loss_suspected()
        if lost is None:
            raise
        import sys as _sys
        print(f"vitax.control: runtime error after losing peer {lost} "
              f"({type(e).__name__}: {e}); exiting {EXIT_HANG} for the "
              f"supervisor to restart from the last committed checkpoint",
              file=_sys.stderr, flush=True)
        raise SystemExit(EXIT_HANG) from e
    finally:
        if prof["on"]:
            jax.profiler.stop_trace()
            master_print(f"profile trace written to {cfg.profile_dir}")
        control.stop()  # liveness threads + any armed peer-loss exit timer
        if arbiter_reporter is not None:
            arbiter_reporter.stop()  # flushes the last committed step
        if watchdog is not None:
            watchdog.stop()  # before the loaders: their drain must not fire it
        train_loader.close()
        val_loader.close()
        if replicator is not None:
            # receiver thread + one final guard-shard pull: an elastic
            # shrink resumes from the survivor's LOCAL store, which must
            # hold the buddy's preemption-save shard before this exit
            replicator.stop()
        if snap_pipe is not None:
            snap_pipe.close()  # drain queued persist/replicate jobs
        from vitax.checkpoint.orbax_io import wait_until_finished
        wait_until_finished()  # drain any in-flight async save before exit
        if recorder is not None:
            recorder.close()
        faults.uninstall()  # fault plans are per-run, like the recorder
        preempt.uninstall()  # restore normal SIGTERM for post-training work

    master_print("training completed")
    return state


def _stream_cursor(loader, epoch: int, next_step: int):
    """The streaming data plane's resume cursor after `next_step` consumed
    batches, or None for loaders without one (ImageFolder/fake). Rides the
    mid-epoch checkpoint sidecar so the resumed run can validate its derived
    position against the shard set that produced the checkpoint."""
    fn = getattr(loader, "cursor_for_step", None)
    return fn(epoch, next_step) if fn is not None else None


def _verify_stream_resume(cfg, train_loader, resume_step: int) -> None:
    """Mid-epoch stream resume: check the sidecar cursor against the position
    this run derives from (seed, epoch, step). The derivation is the source
    of truth — the stored cursor exists to FAIL LOUDLY when the shard set,
    seed, or topology changed underneath the checkpoint (silently feeding
    different records is the failure mode). Process 0 only: the sidecar holds
    process 0's cursor, and a drifted shard manifest is global anyway."""
    if not resume_step or not hasattr(train_loader, "check_cursor"):
        return
    if jax.process_index() != 0:
        return
    from vitax.checkpoint.orbax_io import load_stream_cursor
    cursor = load_stream_cursor(cfg.ckpt_dir, cfg.resume_epoch)
    if cursor is not None:
        train_loader.check_cursor(cursor, resume_step)
        master_print(f"stream resume cursor verified: epoch "
                     f"{cursor.get('epoch')}, shard_cursor "
                     f"{cursor.get('shard_cursor')} "
                     f"({cursor.get('shard')}), record_offset "
                     f"{cursor.get('record_offset')}")


def _elastic_resume(cfg, epoch: int):
    """Resume plan for `epoch` under the CURRENT topology: (resume_step,
    (from, to) process counts when they differ else None, epoch_rounded).
    `epoch_rounded` True means the mid-epoch progress was dropped — the loop
    must RE-ENTER `epoch` from step 0, not treat the save as an epoch
    boundary (which would skip the epoch's remaining records). Process 0
    reads the sidecar and plans (vitax/train/control.py elastic_resume_plan);
    every process adopts its verdict — the same broadcast discipline as the
    auto-resume epoch pick, so a non-atomic shared store can never let hosts
    disagree on where the epoch re-enters."""
    from vitax.checkpoint.orbax_io import load_resume_meta
    from vitax.train.control import elastic_resume_plan
    step = prev = rounded = 0
    if jax.process_index() == 0:
        plan = elastic_resume_plan(load_resume_meta(cfg.ckpt_dir, epoch),
                                   jax.process_count())
        step = plan.resume_step
        rounded = int(plan.epoch_rounded)
        if plan.topology_changed:
            prev = plan.from_processes
            master_print(
                f"elastic resume: checkpoint epoch {epoch} was written by "
                f"{plan.from_processes} process(es), this run has "
                f"{jax.process_count()}"
                + (f" — stream cursor invalidated by the topology change; "
                   f"epoch-rounding the resume (re-running "
                   f"{plan.skipped_steps} mid-epoch steps)"
                   if plan.epoch_rounded else
                   " — rank-interleaved sampling keeps the step-granular "
                   "resume exact"))
    step = distributed.broadcast_from_process0(step)
    prev = distributed.broadcast_from_process0(prev)
    rounded = bool(distributed.broadcast_from_process0(rounded))
    return step, ((prev, jax.process_count()) if prev else None), rounded


def _save_ckpt(cfg, state, epoch, *, wait, step_in_epoch=None,
               stream_cursor=None, snap_pipe=None, replicator=None):
    """Route a checkpoint save through the zero-stall pipeline when one is
    active — ALL saves must: Orbax's async checkpointer is a per-process
    singleton, and a direct save from the loop thread would race the
    pipeline's worker. wait=True keeps its meaning (drain before return —
    final/emergency semantics). Saves under an active replication window
    record the window in the resume sidecar and refresh the peer store."""
    extra = ({"replicate_steps": cfg.replicate_steps}
             if cfg.replicate_steps > 0 else None)
    if snap_pipe is not None:
        snap_pipe.submit(state, epoch=epoch, step_in_epoch=step_in_epoch or 0,
                         stream_cursor=stream_cursor, persist_to=cfg.ckpt_dir,
                         keep=cfg.keep_checkpoints, extra_meta=extra,
                         replicator=replicator, wait=wait)
    else:
        save_state(cfg.ckpt_dir, epoch, state, wait=wait,
                   step_in_epoch=step_in_epoch, stream_cursor=stream_cursor,
                   keep=cfg.keep_checkpoints, extra_meta=extra)


def _run_epochs(  # vtx: ignore[VTX103] `dispatch` IS the submission's time; the fence is a phase of its own
                cfg, state, train_step, train_loader, val_loader, eval_step,
                schedule, smoothed_loss, smoothed_time, prof,
                resume_step: int = 0, resume_rounded: bool = False,
                recorder=None, watchdog=None, control=None,
                snap_pipe=None, replicator=None, arbiter_reporter=None):
    if control is None:  # direct callers (tests): a local, collective-free plane
        control = ControlPlane(sync_steps=cfg.control_sync_steps,
                               watchdog=watchdog)
    data_rng = jax.random.key(cfg.seed + 1)
    total_steps = 0
    # the loop thread's timeline (module docstring): one row of LOOP_MARKS
    # an iteration since the last step record
    marks = []
    # profiler window (historical default: steps 3..7 — start after 2
    # completed steps so the compile step stays out of the trace)
    prof_start = cfg.profile_start_step
    prof_stop = cfg.profile_start_step + cfg.profile_num_steps
    # resume_step > 0: the resume checkpoint was a mid-epoch preemption save —
    # re-enter THAT epoch at the recorded step (the sampler order is a pure
    # function of (seed, epoch), so the data stream continues exactly where
    # the preempted run left off). resume_rounded: the save was ALSO
    # mid-epoch, but a topology change invalidated its stream cursor — the
    # planner dropped the step, so re-enter the SAME epoch from step 0
    # (treating it as an epoch boundary would silently skip the epoch's
    # remaining records, the opposite of the rounding contract).
    reenter = bool(resume_step) or resume_rounded
    start_epoch = cfg.resume_epoch + (0 if reenter else 1)
    if resume_step:
        master_print(f"step-granular resume: re-entering epoch {start_epoch} "
                     f"at step {resume_step + 1}")
        _verify_stream_resume(cfg, train_loader, resume_step)
    elif resume_rounded:
        master_print(f"epoch-rounded resume: re-running epoch {start_epoch} "
                     f"from step 1 (mid-epoch stream cursor invalidated by "
                     f"the topology change)")
    for epoch in range(max(start_epoch, 1), cfg.num_epochs + 1):
        master_print(f"starting epoch {epoch}")
        time_epoch_b = time_step_b = time.time()
        metrics = None
        start_step = resume_step if epoch == start_epoch else 0
        batches = train_loader.epoch(epoch, start_step=start_step)
        for step in itertools.count(start_step):
            if cfg.profile_dir and total_steps == prof_start and not prof["on"]:
                # before t_next: the profiler's start is the last of the
                # iteration before's `host` phase, and the first traced
                # step's annotation opens inside the trace
                jax.profiler.start_trace(cfg.profile_dir)
                prof["on"] = True
            t_next = time.time()
            # the profiler's step view (--profile_dir): the batch fetch and
            # the dispatch of global step `total_steps + 1`, the number its
            # step record carries; a flag test when no trace runs
            with jax.profiler.StepTraceAnnotation("train",
                                                  step_num=total_steps + 1):
                batch = next(batches, None)
                if batch is None or (cfg.steps_per_epoch
                                     and step >= cfg.steps_per_epoch):
                    break
                t_batch = time.time()
                state, metrics = train_step(state, batch, data_rng)
            t_dispatch = time.time()
            total_steps += 1
            # first step of THIS RUN (fresh start, epoch-granular resume, or
            # mid-epoch resume alike): always log it — it carries the compile
            will_log = (total_steps == 1
                        or (step + 1) % cfg.log_step_interval == 0)
            # The FIRST step arms the watchdog — after its results
            # MATERIALIZE, not at dispatch return: the first execution
            # covers XLA compile and, multi-host, collective-transport
            # bring-up + peer compile skew. None of that is a hang, and
            # --hang_timeout_s stays independent of all of it.
            arming = watchdog is not None and not watchdog.running
            closing_trace = prof["on"] and total_steps == prof_stop
            host_loss = None
            t_fence = t_dispatch
            if will_log or arming or closing_trace:
                # the iteration's one fence. At a log step it comes before
                # the clock is read: train_step returns at dispatch, so an
                # unfenced delta times the async enqueue, not device
                # execution — the logged sec/iter would converge to dispatch
                # latency while the devices fall arbitrarily far behind.
                # Fetched ONCE here and passed through as a host value
                # (_run_logging and the telemetry record reuse it); every
                # other step stays fence-free so the pipeline keeps its
                # device/host overlap.
                host_loss = float(jax.device_get(metrics["loss"]))
                t_fence = time.time()
            # the loader stamps t_got where its queue hands a host batch
            # over; one without a queue never does, and `put` is all its time
            marks.append([total_steps, t_next,
                          max(getattr(train_loader, "t_got", 0.0), t_next),
                          t_batch, t_dispatch, t_fence])
            # fault drill point (no-op without a plan): fires BEFORE the pet
            # so an injected hang starves the watchdog exactly like a real
            # wedged step; index = the global step count, so plans are
            # deterministic across restarts of the same config
            faults.fire("step", index=total_steps)
            if arming:
                watchdog.start()
            elif watchdog is not None:
                # pet on dispatch, not completion: the loop is alive; a wedged
                # DEVICE stalls the next log step's fence, which stops pets
                # within log_step_interval dispatches (async dispatch depth)
                watchdog.pet()
            if closing_trace:   # fenced above: the traced steps are done
                jax.profiler.stop_trace()
                prof["on"] = False
                master_print(f"profile trace written to {cfg.profile_dir}")
            smoothed_time.update(t_fence - time_step_b, batch_size=1)
            time_step_b = t_fence
            if will_log:
                lr = float(schedule(int(jax.device_get(metrics["lr_step"]))))
                _run_logging(cfg, epoch, step, host_loss, lr, smoothed_loss,
                             smoothed_time)
                if recorder is not None:
                    # all inputs are already host values; the one extra
                    # device->host fetch (grad_norm) rides a log step that
                    # just fenced — non-log steps stay untouched
                    recorder.record_step(
                        step=total_steps, epoch=epoch, step_in_epoch=step + 1,
                        loss=host_loss, lr=lr,
                        sec_per_iter=smoothed_time.avg,
                        data_wait_s=(sum(m[2] - m[1] for m in marks)
                                     / len(marks)),
                        loop_marks=marks,
                        ckpt_stall_s=(snap_pipe.consume_stall_s() / len(marks)
                                      if snap_pipe is not None else 0.0),
                        grad_norm=float(jax.device_get(metrics["grad_norm"])),
                        packed_counts=(
                            {k: float(jax.device_get(metrics[k])) for k in
                             (DECODER_COUNTERS if cfg.decoder else
                              PACKED_COUNTERS) if k in metrics}
                            if cfg.packed else None),
                        expert_load=(
                            jax.device_get(
                                metrics["expert_load"]).tolist()
                            if cfg.decoder else None))
                    if "kl" in metrics:
                        # distill step (vitax/programs/workloads.py): the
                        # extra metrics ride the log-step fence the record
                        # above just paid
                        recorder.event(
                            "distill", step=total_steps, epoch=epoch,
                            kl=float(jax.device_get(metrics["kl"])),
                            ce=float(jax.device_get(metrics["ce"])),
                            teacher_top1=float(
                                jax.device_get(metrics["teacher_top1"])),
                            student_top1=float(
                                jax.device_get(metrics["student_top1"])),
                            alpha=cfg.distill_alpha, temp=cfg.distill_temp)
                marks = []   # taken always, written where a recorder is
            if arbiter_reporter is not None:
                # a lock + three assignments; the reporter thread posts
                arbiter_reporter.update(total_steps, epoch)
            if (replicator is not None and snap_pipe is not None
                    and (step + 1) % cfg.replicate_steps == 0):
                # replication window: stage this host's shard (the only part
                # on the loop thread — charged to ckpt_stall_s) and mirror
                # it to the ring buddy from the pipeline worker
                snap_pipe.submit(
                    state, epoch=epoch, step_in_epoch=step + 1,
                    stream_cursor=_stream_cursor(train_loader, epoch,
                                                 step + 1),
                    replicator=replicator)
            # step-boundary control poll (vitax/train/control.py): folds the
            # watchdog's escalation flag, the SIGTERM flag, and fault/peer
            # bits into one word — agreed across hosts on the sync cadence,
            # free local reads single-host — so every host reacts to the
            # SAME verdict at the SAME step
            sig = control.poll(step_in_epoch=step, epoch=epoch)
            if sig.emergency:
                # agreed hang escalation / fault / peer-loss verdict: save a
                # jointly committed mid-epoch checkpoint and exit EXIT_HANG
                # on ALL hosts for the supervisor to restart. Reaching this
                # agreement proves every process is alive, so the joint
                # save's collectives line up; the acknowledge re-arms the
                # watchdog's hard deadline so a save wedged on a truly dead
                # device is still bounded.
                if watchdog is not None:
                    watchdog.acknowledge_escalation()
                master_print(f"watchdog escalation: saving emergency "
                             f"checkpoint at epoch {epoch} (step {step + 1}) "
                             f"and exiting with code {EXIT_HANG} "
                             f"(agreed signals: {sig.describe()})")
                jax.device_get(metrics["loss"])  # fence: step must be done
                _save_ckpt(cfg, state, epoch, wait=True,
                           step_in_epoch=step + 1,
                           stream_cursor=_stream_cursor(train_loader, epoch,
                                                        step + 1),
                           snap_pipe=snap_pipe, replicator=replicator)
                control.arm_exit_deadline()  # bound the barrier: a peer
                # dead mid-drain must not wedge survivors forever
                distributed.barrier("coordinated emergency exit")
                raise SystemExit(EXIT_HANG)
            if sig.preempt:
                # commit a synchronous save of the live mid-epoch state under
                # this epoch's name (with the completed step count in the
                # resume sidecar), drain, and leave. Auto-resume
                # (--resume_epoch -1) restarts INSIDE this epoch at the next
                # step — no data is skipped or repeated.
                master_print(f"SIGTERM received: saving preemption checkpoint "
                             f"at epoch {epoch} (step {step + 1}) and exiting")
                jax.device_get(metrics["loss"])  # fence: step must be done
                _save_ckpt(cfg, state, epoch, wait=True,
                           step_in_epoch=step + 1,
                           stream_cursor=_stream_cursor(train_loader, epoch,
                                                        step + 1),
                           snap_pipe=snap_pipe, replicator=replicator)
                # bounded: a peer that died mid-save must not wedge this
                # host in the barrier forever — the plane prefers the
                # watchdog's hard deadline when one runs and otherwise arms
                # its own DEFAULT_EXIT_DEADLINE_S timer, so the barrier is
                # bounded under EVERY config (the PR 10 gap, closed)
                control.arm_exit_deadline()
                distributed.barrier("coordinated preemption exit")
                return state
            if cfg.max_steps and total_steps >= cfg.max_steps:
                break
        batches.close()   # a loader left early stops its producer here

        if metrics is not None:
            jax.device_get(metrics["loss"])  # fence: honest epoch wall time
        master_print(f"epoch {epoch} done ({time.time() - time_epoch_b:.2f} sec)")

        # epoch boundary: always sync — epochs shorter than the in-loop
        # cadence still get an agreed verdict here (every host reaches the
        # boundary at the same point)
        sig = control.poll(step_in_epoch=None, epoch=epoch)
        if sig.emergency:
            if watchdog is not None:
                watchdog.acknowledge_escalation()
            master_print(f"watchdog escalation: saving emergency checkpoint "
                         f"after epoch {epoch} and exiting with code "
                         f"{EXIT_HANG} (agreed signals: {sig.describe()})")
            _save_ckpt(cfg, state, epoch, wait=True,
                       snap_pipe=snap_pipe, replicator=replicator)
            control.arm_exit_deadline()  # bound the barrier (see above)
            distributed.barrier("coordinated emergency exit")
            raise SystemExit(EXIT_HANG)
        if sig.preempt:
            master_print(f"SIGTERM received: saving preemption checkpoint "
                         f"after epoch {epoch} and exiting")
            _save_ckpt(cfg, state, epoch, wait=True,
                       snap_pipe=snap_pipe, replicator=replicator)
            control.arm_exit_deadline()  # bound the barrier (see above)
            distributed.barrier("coordinated preemption exit")
            return state

        if epoch % cfg.ckpt_epoch_interval == 0 or epoch == cfg.num_epochs:
            # async: the device->host snapshot happens before return, the write
            # commits in background while the next epoch trains; the final save
            # waits so training never exits with an uncommitted checkpoint.
            # Under --zero_stall_ckpt even the snapshot leaves the loop thread
            # after a staged memcpy (vitax/checkpoint/snapshot.py).
            _save_ckpt(cfg, state, epoch, wait=epoch == cfg.num_epochs,
                       snap_pipe=snap_pipe, replicator=replicator)
        # evaluation over packed rows is not built (PERF.md section 7)
        if not cfg.packed and (epoch % cfg.test_epoch_interval == 0
                               or epoch == cfg.num_epochs):
            top1, top5, _, _ = eval_on_val(cfg, val_loader, eval_step, state,
                                           recorder=recorder, epoch=epoch)
            master_print(f"accuracy on val: {top1:.4f} (top-5 {top5:.4f})")
        if cfg.max_steps and total_steps >= cfg.max_steps:
            break

    return state


def _run_logging(cfg, epoch, step, loss, lr, smoothed_loss, smoothed_time):
    """Throttled step log (reference run_logging, run_vit_training.py:203-213).

    The loss is already the global-batch mean — the reference's
    mesh_reduce(sum)/world_size (:205-206) is compiled into the step. The
    caller fetched it (and resolved lr) once at the log-step fence and passes
    the host values through — no second device->host sync here."""
    smoothed_loss.update(loss, batch_size=1)
    mem = f", {memory_summary()}" if cfg.log_memory else ""
    master_print(
        f"epoch {epoch} step {step + 1}, lr: {lr:.4f}, "
        f"loss: {smoothed_loss.avg:.4f}, "
        f"sec/iter: {smoothed_time.avg:.4f}{mem}"
    )


def eval_on_val(cfg: Config, val_loader, eval_step, state: TrainState,
                recorder=None, epoch: int = 0):
    """Top-1 + top-5 accuracy over the val split (reference eval_on_val,
    run_vit_training.py:306-318, extended with the top-5 metric the serving
    stack reports). drop_last semantics preserved: the remainder of the
    split is ignored, exactly like the reference (:77,:83).

    With a Recorder (--metrics_dir), emits one kind:"eval" telemetry event
    (epoch, top1, top5, n) per eval pass — tools/metrics_report.py surfaces
    the latest one. Returns (top1, top5, n_correct, total)."""
    correct = None
    total = 0
    for step, batch in enumerate(val_loader.epoch(0)):
        if cfg.eval_max_batches and step >= cfg.eval_max_batches:
            break
        c = eval_step(state, batch)
        correct = c if correct is None else jax.tree.map(
            lambda a, b: a + b, correct, c)
        total += cfg.batch_size
    counts = (jax.device_get(correct) if correct is not None
              else {"correct": 0, "correct_top5": 0})
    n_correct = int(counts["correct"])
    n_top5 = int(counts["correct_top5"])
    top1 = n_correct / total if total else 0.0
    top5 = n_top5 / total if total else 0.0
    if recorder is not None:
        recorder.event("eval", epoch=int(epoch), top1=top1, top5=top5,
                       n=total)
    return top1, top5, n_correct, total
