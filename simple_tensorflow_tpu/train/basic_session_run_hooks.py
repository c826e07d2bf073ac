"""Standard hooks (ref: tensorflow/python/training/basic_session_run_hooks.py)."""

from __future__ import annotations

import time

import numpy as np

from ..framework import errors
from ..platform import tf_logging as logging
from . import session_run_hook
from . import training_util

SessionRunHook = session_run_hook.SessionRunHook
SessionRunArgs = session_run_hook.SessionRunArgs


class SecondOrStepTimer:
    """(ref: basic_session_run_hooks.py:48)."""

    def __init__(self, every_secs=None, every_steps=None):
        if (every_secs is None) == (every_steps is None):
            raise ValueError("exactly one of every_secs/every_steps required")
        self._every_secs = every_secs
        self._every_steps = every_steps
        self._last_time = None
        self._last_step = None

    def should_trigger_for_step(self, step):
        if self._last_step is None:
            return True
        if step == self._last_step:
            return False
        if self._every_secs is not None:
            return time.time() >= self._last_time + self._every_secs
        return step >= self._last_step + self._every_steps

    def update_last_triggered_step(self, step):
        now = time.time()
        elapsed_secs = None if self._last_time is None else now - self._last_time
        elapsed_steps = None if self._last_step is None else step - self._last_step
        self._last_time, self._last_step = now, step
        return elapsed_secs, elapsed_steps

    def last_triggered_step(self):
        return self._last_step

    @property
    def every_steps(self):
        return self._every_steps

    def steps_until_trigger(self, step):
        """Steps until this timer next fires — the hook's fusion-window
        vote (session_run_hook.SessionRunHook.until_next_trigger). 1
        when time-based (a wall-clock trigger cannot be predicted in
        steps) or when the timer has never fired (it wants the next
        boundary). The returned window ENDS at the trigger step —
        CheckpointSaver/StepCounter/SummarySaver observe the boundary
        value and fuse onward. ProfilerHook aligns differently (its
        window must START at the trigger so the whole window is traced)
        and implements its own vote."""
        if self._every_steps is None or self._last_step is None:
            return 1
        return max(1, self._last_step + self._every_steps - step)


class StopAtStepHook(SessionRunHook):
    """(ref: basic_session_run_hooks.py:331)."""

    def __init__(self, num_steps=None, last_step=None):
        if (num_steps is None) == (last_step is None):
            raise ValueError("exactly one of num_steps/last_step required")
        self._num_steps = num_steps
        self._last_step = last_step
        self._global_step_tensor = None

    def begin(self):
        self._global_step_tensor = training_util.get_global_step()
        if self._global_step_tensor is None:
            raise RuntimeError("Global step must be created for StopAtStepHook")

    def after_create_session(self, session, coord):
        if self._last_step is None:
            gs = int(np.asarray(session.run(self._global_step_tensor._ref)))
            self._last_step = gs + self._num_steps

    def before_run(self, run_context):
        return SessionRunArgs(self._global_step_tensor._ref)

    def after_run(self, run_context, run_values):
        gs = int(np.asarray(run_values.results))
        if gs >= self._last_step:
            run_context.request_stop()

    def until_next_trigger(self, global_step):
        # a fused window must not overshoot the stop step
        if self._last_step is None:
            return 1
        return max(1, self._last_step - global_step)


class CheckpointSaverHook(SessionRunHook):
    """(ref: basic_session_run_hooks.py:404).

    Saves are ASYNC by default (``save_async=True``, stf.checkpoint):
    trigger steps pay only the barrier snapshot — donation-safe device
    copies + host state — and the ``stf_ckpt_writer`` thread commits
    while the next fused window runs. ``end()`` (and a blocking save)
    drains the writer, so every checkpoint is durable before the
    session closes. Fusion votes are unchanged: windows still split
    exactly at save boundaries. ``save_async=False`` or a non-native
    Saver backend restores the in-line blocking behavior."""

    def __init__(self, checkpoint_dir, save_secs=None, save_steps=None,
                 saver=None, checkpoint_basename="model.ckpt", scaffold=None,
                 listeners=None, save_async=True):
        import os

        self._checkpoint_dir = checkpoint_dir
        self._save_path = os.path.join(checkpoint_dir, checkpoint_basename)
        self._saver = saver
        self._scaffold = scaffold
        self._timer = SecondOrStepTimer(every_secs=save_secs,
                                        every_steps=save_steps)
        self._listeners = listeners or []
        self._save_async = save_async
        self._async_engine = None

    def begin(self):
        self._global_step_tensor = training_util.get_global_step()
        if self._global_step_tensor is None:
            raise RuntimeError("Global step required for CheckpointSaverHook")
        for l in self._listeners:
            l.begin()

    def _get_saver(self):
        if self._saver is not None:
            return self._saver
        if self._scaffold is not None and self._scaffold.saver is not None:
            return self._scaffold.saver
        from .saver import Saver

        self._saver = Saver()
        return self._saver

    def after_create_session(self, session, coord):
        self._save(session, int(np.asarray(
            session.run(self._global_step_tensor._ref))))

    def before_run(self, run_context):
        return SessionRunArgs(self._global_step_tensor._ref)

    def after_run(self, run_context, run_values):
        step = int(np.asarray(run_values.results))
        if self._timer.should_trigger_for_step(step):
            self._timer.update_last_triggered_step(step)
            self._save(run_context.session, step)

    def until_next_trigger(self, global_step):
        # checkpoints at step boundaries inside a fused window force the
        # window to split at the save step
        return self._timer.steps_until_trigger(global_step)

    def end(self, session):
        # final save is BLOCKING: the process may exit right after, so
        # the writer queue must be drained before end() returns
        self._save(session, int(np.asarray(
            session.run(self._global_step_tensor._ref))),
            blocking=True)

    def _engine_for(self, saver):
        """The async engine for this hook's saver, or None when saves
        should go through ``saver.save`` directly (save_async=False, a
        non-native backend, or a backend="async" saver that already is
        its own engine)."""
        if not self._save_async:
            return None
        if getattr(saver, "_backend", None) != "native":
            return None
        if self._async_engine is None:
            from ..checkpoint.manager import AsyncSaverEngine

            self._async_engine = AsyncSaverEngine(saver)
        return self._async_engine

    def _save(self, session, step, blocking=False):
        for l in self._listeners:
            l.before_save(session, step)
        saver = self._get_saver()
        engine = self._engine_for(saver)
        if engine is not None:
            engine.save(session, self._save_path, global_step=step)
            if blocking:
                engine.wait_until_finished()
        else:
            saver.save(session, self._save_path, global_step=step)
            if blocking and hasattr(saver, "wait_until_finished"):
                saver.wait_until_finished()
        for l in self._listeners:
            l.after_save(session, step)


class CheckpointSaverListener:
    def begin(self):
        pass

    def before_save(self, session, global_step_value):
        pass

    def after_save(self, session, global_step_value):
        pass

    def end(self, session, global_step_value):
        pass


class StepCounterHook(SessionRunHook):
    """(ref: basic_session_run_hooks.py:547) — also reports steps/sec,
    and closes the perf loop: MFU plus measured-over-predicted step time
    from the static cost model over the caller's fetches
    (framework/cost_model.predicted_vs_measured + utils/perf; MFU per
    Kumar et al., arXiv:1909.09756). ``last_perf`` keeps the latest
    report for programmatic consumers."""

    def __init__(self, every_n_steps=100, every_n_secs=None, output_dir=None,
                 summary_writer=None, report_mfu=True):
        self._timer = SecondOrStepTimer(every_secs=every_n_secs,
                                        every_steps=every_n_steps
                                        if every_n_secs is None else None)
        self._summary_writer = summary_writer
        self._output_dir = output_dir
        self._report_mfu = report_mfu
        self._est_cache = None  # (key, CostEstimate): graph walk done once
        self.last_steps_per_sec = None
        self.last_perf = None

    def begin(self):
        self._global_step_tensor = training_util.get_global_step()
        if self._summary_writer is None and self._output_dir:
            from ..summary.writer.writer import FileWriter

            self._summary_writer = FileWriter(self._output_dir)

    def before_run(self, run_context):
        return SessionRunArgs(self._global_step_tensor._ref)

    def until_next_trigger(self, global_step):
        # only needs global_step at its reporting boundary: a fused
        # window up to the next report keeps steps/sec exact (steps are
        # counted from the global_step delta, not from run calls)
        return self._timer.steps_until_trigger(global_step)

    def _perf_report(self, run_context, sec_per_step):
        """Best-effort: the caller's fetches drive the cost model; a
        fetch the model can't cost must never break the training loop."""
        try:
            from ..framework import cost_model
            from ..framework import graph as ops_mod
            from ..utils import nest

            items = [f for f in nest.flatten(run_context.original_args.fetches)
                     if isinstance(f, (ops_mod.Tensor, ops_mod.Operation))
                     or hasattr(f, "_ref")]
            if not items:
                return None
            # the static estimate is a full graph walk — cache it per
            # (fetches, rewrite_version) so every trigger only pays the
            # measured-side arithmetic
            graph = run_context.session.graph
            key = (tuple(id(i) for i in items),
                   getattr(graph, "_rewrite_version", 0))
            if self._est_cache is None or self._est_cache[0] != key:
                self._est_cache = (key, cost_model.estimate(items))
            return cost_model.predicted_vs_measured(
                items, measured_seconds=sec_per_step,
                est=self._est_cache[1])
        except Exception:
            return None

    def after_run(self, run_context, run_values):
        step = int(np.asarray(run_values.results))
        if self._timer.should_trigger_for_step(step):
            secs, steps = self._timer.update_last_triggered_step(step)
            if secs is not None and secs > 0:
                self.last_steps_per_sec = steps / secs
                logging.info("global_step/sec: %.4g", self.last_steps_per_sec)
                perf_report = (self._perf_report(run_context, secs / steps)
                               if self._report_mfu else None)
                if perf_report is not None:
                    self.last_perf = perf_report
                    logging.info(
                        "perf: mfu=%s measured/predicted=%.3g",
                        perf_report.get("mfu", "not measured"),
                        perf_report.get("measured_over_predicted", 0.0))
                if self._summary_writer is not None:
                    self._summary_writer.add_summary_value(
                        "global_step/sec", self.last_steps_per_sec, step)
                    if perf_report is not None:
                        if "mfu" in perf_report:
                            self._summary_writer.add_summary_value(
                                "perf/mfu", perf_report["mfu"], step)
                        if "measured_over_predicted" in perf_report:
                            self._summary_writer.add_summary_value(
                                "perf/measured_over_predicted",
                                perf_report["measured_over_predicted"],
                                step)


class LoggingTensorHook(SessionRunHook):
    """(ref: basic_session_run_hooks.py:167)."""

    def __init__(self, tensors, every_n_iter=None, every_n_secs=None,
                 at_end=False, formatter=None):
        if isinstance(tensors, dict):
            self._tag_order = list(tensors)
            self._tensors = tensors
        else:
            self._tag_order = [getattr(t, "name", str(i))
                               for i, t in enumerate(tensors)]
            self._tensors = dict(zip(self._tag_order, tensors))
        self._formatter = formatter
        self._timer = SecondOrStepTimer(every_secs=every_n_secs,
                                        every_steps=every_n_iter)
        self._at_end = at_end
        self._iter = 0

    def before_run(self, run_context):
        self._should_log = self._timer.should_trigger_for_step(self._iter)
        if self._should_log:
            return SessionRunArgs(self._tensors)
        return None

    def after_run(self, run_context, run_values):
        if self._should_log:
            self._timer.update_last_triggered_step(self._iter)
            vals = run_values.results
            if self._formatter:
                logging.info(self._formatter(vals))
            else:
                logging.info(", ".join(
                    f"{tag} = {vals[tag]}" for tag in self._tag_order))
        self._iter += 1

    def end(self, session):
        if self._at_end:
            vals = session.run(self._tensors)
            logging.info(", ".join(
                f"{tag} = {vals[tag]}" for tag in self._tag_order))


class NanLossDuringTrainingError(RuntimeError):
    pass


class NanTensorHook(SessionRunHook):
    """(ref: basic_session_run_hooks.py:635)."""

    def __init__(self, loss_tensor, fail_on_nan_loss=True):
        self._loss_tensor = loss_tensor
        self._fail = fail_on_nan_loss

    def before_run(self, run_context):
        return SessionRunArgs(self._loss_tensor)

    def after_run(self, run_context, run_values):
        if np.isnan(np.asarray(run_values.results)).any():
            if self._fail:
                raise NanLossDuringTrainingError("NaN loss during training.")
            logging.warning("NaN loss; stopping training.")
            run_context.request_stop()


class SummarySaverHook(SessionRunHook):
    """(ref: basic_session_run_hooks.py:683)."""

    def __init__(self, save_steps=None, save_secs=None, output_dir=None,
                 summary_writer=None, scaffold=None, summary_op=None):
        self._summary_op = summary_op
        self._scaffold = scaffold
        self._output_dir = output_dir
        self._summary_writer = summary_writer
        self._timer = SecondOrStepTimer(every_secs=save_secs,
                                        every_steps=save_steps)

    def begin(self):
        self._global_step_tensor = training_util.get_global_step()
        if self._summary_writer is None and self._output_dir:
            from ..summary.writer.writer import FileWriter

            self._summary_writer = FileWriter(self._output_dir)

    def _get_op(self):
        if self._summary_op is not None:
            return self._summary_op
        if self._scaffold is not None:
            return self._scaffold.summary_op
        from ..summary import summary as summary_mod

        return summary_mod.merge_all()

    def before_run(self, run_context):
        op = self._get_op()
        self._should = (op is not None and
                        self._timer.should_trigger_for_step(
                            self._timer.last_triggered_step() or 0) or
                        self._timer.last_triggered_step() is None)
        fetches = {"step": self._global_step_tensor._ref}
        if self._should and op is not None:
            fetches["summary"] = op
        return SessionRunArgs(fetches)

    def after_run(self, run_context, run_values):
        step = int(np.asarray(run_values.results["step"]))
        if "summary" in run_values.results and self._summary_writer:
            if self._timer.should_trigger_for_step(step):
                self._timer.update_last_triggered_step(step)
                self._summary_writer.add_summary(
                    run_values.results["summary"], step)

    def until_next_trigger(self, global_step):
        # summaries evaluate at the window boundary; a save step inside
        # the window splits it (also: a summary fetch makes the plan a
        # host sink, so the boundary step itself runs unfused)
        return self._timer.steps_until_trigger(global_step)

    def end(self, session):
        if self._summary_writer:
            self._summary_writer.flush()


class GlobalStepWaiterHook(SessionRunHook):
    """(ref: basic_session_run_hooks.py:775)."""

    def __init__(self, wait_until_step):
        self._wait_until_step = wait_until_step

    def begin(self):
        self._global_step_tensor = training_util.get_global_step()

    def until_next_trigger(self, global_step):
        return 1 << 30  # waits BEFORE runs; no per-step observation

    def before_run(self, run_context):
        if self._wait_until_step <= 0:
            return None
        while True:
            gs = int(np.asarray(run_context.session.run(
                self._global_step_tensor._ref)))
            if gs >= self._wait_until_step:
                return None
            time.sleep(0.5)


class FinalOpsHook(SessionRunHook):
    """(ref: basic_session_run_hooks.py:812)."""

    def __init__(self, final_ops, final_ops_feed_dict=None):
        self._final_ops = final_ops
        self._feed = final_ops_feed_dict
        self.final_ops_values = None

    def until_next_trigger(self, global_step):
        return 1 << 30  # only acts at end()

    def end(self, session):
        if self._final_ops is not None:
            self.final_ops_values = session.run(self._final_ops,
                                                feed_dict=self._feed)


class FeedFnHook(SessionRunHook):
    def __init__(self, feed_fn):
        self._feed_fn = feed_fn

    def before_run(self, run_context):
        return SessionRunArgs(fetches=None, feed_dict=self._feed_fn())


class ProfilerHook(SessionRunHook):
    """(ref: basic_session_run_hooks.py:846): requests a
    ``SOFTWARE_TRACE`` run on trigger steps and writes the resulting
    step-stats timeline as ``timeline-<step>.json`` chrome traces
    (load in Perfetto / chrome://tracing). Logs the traced step's MFU
    from the executable's XLA cost analysis when available.
    ``use_jax_profiler=True`` additionally wraps trigger steps in a
    jax.profiler trace (the XLA-kernel-level view)."""

    def __init__(self, save_steps=None, save_secs=None,
                 output_dir="", show_dataflow=True, show_memory=False,
                 use_jax_profiler=False):
        self._output_dir = output_dir or "."
        self._timer = SecondOrStepTimer(every_secs=save_secs,
                                        every_steps=save_steps)
        self._show_dataflow = show_dataflow
        self._show_memory = show_memory
        self._use_jax_profiler = use_jax_profiler
        self._jax_tracing = False
        self._request_summary = False
        self._next_step = None
        self.last_trace_path = None

    def begin(self):
        self._global_step_tensor = training_util.get_global_step()
        self._next_step = None

    def until_next_trigger(self, global_step):
        # ISSUE 8 satellite: the profiler's window must START at its
        # trigger so the run it arms (SOFTWARE_TRACE via before_run) is
        # one whole fused window — previously the armed trigger either
        # vanished into an untraced window or silently forced a single
        # unfused step. Away from the trigger, vote the distance to the
        # step BEFORE it (the next window then begins exactly at the
        # trigger); at the trigger (or before any trigger), vote the
        # full cadence. run_steps records the window's spans + per-op
        # attribution under SOFTWARE_TRACE, and _save annotates the
        # timeline with the window's global-step range.
        every = self._timer.every_steps
        if every is None:
            return 1  # time-based: a wall-clock trigger is unpredictable
        last = self._timer.last_triggered_step()
        next_step = global_step + 1  # first step of the window voted on
        if last is None or next_step >= last + every:
            return every
        return last + every - next_step

    def before_run(self, run_context):
        self._request_summary = (
            self._next_step is None
            or self._timer.should_trigger_for_step(self._next_step))
        opts = None
        if self._request_summary:
            from ..client.session import RunOptions

            opts = RunOptions(trace_level=RunOptions.SOFTWARE_TRACE)
            if self._use_jax_profiler and not self._jax_tracing:
                import jax

                try:
                    jax.profiler.start_trace(self._output_dir)
                    self._jax_tracing = True
                except Exception:
                    pass
        return SessionRunArgs(self._global_step_tensor._ref, options=opts)

    def after_run(self, run_context, run_values):
        step = int(np.asarray(run_values.results))
        if self._request_summary:
            # anchor the cadence at the traced WINDOW'S START, not its
            # end: with update-at-end, save_steps=N under fusion would
            # stretch the real period to ~2N-1 (N to the next trigger
            # PLUS the window the timer just swallowed). Anchored at the
            # start, trace windows begin exactly every N steps.
            start = step
            md = run_values.run_metadata
            fusion = (getattr(md, "step_stats", None) or {}).get(
                "loop_fusion") or {}
            if fusion.get("fused") and fusion.get("n_steps"):
                start = step - int(fusion["n_steps"]) + 1
            self._timer.update_last_triggered_step(start)
            if run_values.run_metadata is not None:
                self._save(step, run_values.run_metadata)
            if self._jax_tracing:
                import jax

                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
                self._jax_tracing = False
        self._next_step = step + 1

    def _save(self, step, run_metadata):
        import os

        from ..client.timeline import Timeline

        os.makedirs(self._output_dir, exist_ok=True)
        path = os.path.join(self._output_dir, f"timeline-{step}.json")
        stats0 = getattr(run_metadata, "step_stats", None)
        fusion = (stats0 or {}).get("loop_fusion") or {}
        if fusion.get("fused") and fusion.get("n_steps"):
            # the trace covers a fused window ending at `step`: annotate
            # the timeline with the window's global-step range so the
            # reader knows which steps the one fused bar spans
            n = int(fusion["n_steps"])
            stats0["window_steps"] = [step - n + 1, step]
        with open(path, "w") as f:
            f.write(Timeline(run_metadata).generate_chrome_trace_format(
                show_dataflow=self._show_dataflow,
                show_memory=self._show_memory))
        self.last_trace_path = path
        stats = getattr(run_metadata, "step_stats", None) or {}
        cost = getattr(run_metadata, "cost_graph", None) or {}
        wall = stats.get("wall_time_s")
        from ..utils import perf

        if wall and cost.get("flops") and perf.has_peak():
            logging.info(
                "ProfilerHook step %d: wall=%.4gs xla_flops=%.3g "
                "mfu=%.4g trace=%s", step, wall, cost["flops"],
                perf.mfu(cost["flops"], wall), path)
        else:
            logging.info("ProfilerHook step %d: trace=%s", step, path)
