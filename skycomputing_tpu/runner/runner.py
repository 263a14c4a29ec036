"""The training loop.

Parity with ``scaelum/runner/runner.py:15-156``: epoch/iter loop over a
dataloader with hook dispatch and per-phase wall-clock logging.  The
reference's per-iteration work — RPC pipeline forward, host-side loss,
``dist_autograd.backward``, ``DistributedOptimizer.step`` — collapses into
``PipelineModel.train_step`` (compiled per-stage programs + host-threaded
cotangents).  Reference bugs fixed rather than ported: the ``max_epochs``
property typo (``runner.py:83-85``) and the ``>`` off-by-one in the max-iter
check (``runner.py:119``) which ran max_iters+1 iterations.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax

from ..dynamics import ParameterServer, WorkerManager
from ..ops import build_loss
from ..parallel import PipelineModel
from ..telemetry import (
    LiveMetricsMixin,
    MetricsRegistry,
    span_sinks,
    trace_span,
)
from ..utils import (
    DistributedTimer,
    Logger,
    PhaseTimer,
    enable_persistent_compilation_cache,
)
from .hooks import Hook

_EXHAUSTED = object()  # the loader has no further batch


class Runner(LiveMetricsMixin):
    def __init__(
        self,
        model: PipelineModel,
        parameter_server: ParameterServer,
        worker_manager: WorkerManager,
        max_epochs: int,
        max_iters: int,
        loss_cfg: Optional[Dict] = None,
        timer_cfg: Optional[Dict] = None,
        logging_cfg: Optional[Dict] = None,
        seed: int = 0,
        preflight: bool = True,
    ):
        self.model = model
        self.parameter_server = parameter_server
        self.worker_manager = worker_manager
        # persistent XLA compile cache: a relaunched/re-formed trainer (or
        # a repeated run of the same config) reuses serialized executables
        # instead of recompiling every stage program.  Placement and the
        # off switch: utils/compile_cache.py.
        self.compilation_cache_dir = enable_persistent_compilation_cache()

        self._hooks: List[Hook] = []
        self._epoch = 0
        self._iter = 0
        self._inner_iter = 0
        self._max_epochs = max_epochs
        self._max_iters = max_iters
        self._stop = False
        self._rng = jax.random.key(seed)
        # pre-flight plan verification (analysis/plan_check): abstractly
        # check stage-boundary shapes, memory fit and donation aliasing
        # against the first real batch BEFORE the first train step — i.e.
        # before any XLA compile.  SKYTPU_PREFLIGHT=0 (or preflight=False)
        # opts out.
        self._preflight_enabled = preflight
        self._preflight_done = False

        self._logger = Logger(**(logging_cfg or {}))
        self._timer = DistributedTimer(**(timer_cfg or {}))
        self.phase_timer = PhaseTimer()
        # unified metrics surface: hooks and external pollers read the
        # pipeline's per-step counters through one snapshot() contract
        # (the callable form survives the model rebinding `stats` to a
        # fresh PipelineStats every step)
        self.metrics = MetricsRegistry()
        self.metrics.register(
            "pipeline", lambda: self.model.stats.snapshot(),
            types=getattr(type(getattr(self.model, "stats", None)),
                          "FIELD_TYPES", None),
        )
        # live observability (LiveMetricsMixin: enable_timeseries /
        # start_exporter — opt-in, zero-cost until enabled; the train
        # loop samples the series once per iteration when attached)
        self.timeseries = None
        self._exporter = None
        self.data_loader = None
        # the in-flight (data, labels) pair, stashed for hooks that need a
        # representative batch (SelfHealHook probes stage times with it)
        self.current_batch = None

        if loss_cfg is not None:
            # the model already owns a loss; loss_cfg overrides it (and
            # recompiles the loss program so stale traces can't survive)
            self.model.set_loss_fn(build_loss(loss_cfg))

    # --- state --------------------------------------------------------------
    @property
    def hooks(self) -> List[Hook]:
        return self._hooks

    @property
    def epoch(self) -> int:
        return self._epoch

    @epoch.setter
    def epoch(self, value: int) -> None:
        self._epoch = value

    @property
    def iter(self) -> int:
        return self._iter

    @iter.setter
    def iter(self, value: int) -> None:
        self._iter = value

    @property
    def inner_iter(self) -> int:
        return self._inner_iter

    @property
    def max_epochs(self) -> int:
        return self._max_epochs

    @property
    def max_iters(self) -> int:
        return self._max_iters

    # legacy singular alias (reference exposed ``max_iter``)
    @property
    def max_iter(self) -> int:
        return self._max_iters

    @property
    def timer(self) -> DistributedTimer:
        return self._timer

    @property
    def logger(self) -> Logger:
        return self._logger

    def request_stop(self) -> None:
        """Cooperative stop: finishes the current iteration then exits."""
        self._stop = True

    # --- rng stream (checkpointable) ----------------------------------------
    def snapshot_rng(self):
        """Raw key data of the step-rng split chain, for checkpointing."""
        import numpy as np

        return np.asarray(jax.random.key_data(self._rng))

    def restore_rng(self, key_data) -> None:
        self._rng = jax.random.wrap_key_data(jax.numpy.asarray(key_data))

    # --- pre-flight ---------------------------------------------------------
    def rearm_preflight(self) -> None:
        """Re-run plan verification before the next train step.

        Called after anything that changes the plan mid-run (the
        SelfHealHook's in-process re-allocation rebuild): the NEW
        allocation must be verified exactly like the original was.
        """
        self._preflight_done = False

    def _preflight(self, data) -> None:
        """One-time abstract plan verification against the first batch.

        Runs before the first ``train_step`` (jit compiles lazily, so
        this is before any compile): a malformed allocation — a stage
        boundary that doesn't type-check, an over-budget slice, a
        donation alias that cannot hold — is rejected here with a
        precise diagnostic instead of minutes later inside XLA.
        """
        if self._preflight_done:
            return
        import os

        if not self._preflight_enabled or \
                os.environ.get("SKYTPU_PREFLIGHT", "1") == "0":
            self._preflight_done = True
            return
        from ..analysis.plan_check import has_plan, verify_pipeline

        if not has_plan(self.model):
            # a model type that exposes no allocation (no worker manager,
            # not a replica wrapper) has no plan to verify
            self._logger.info(
                f"pre-flight: skipped — "
                f"{type(self.model).__name__} exposes no allocation"
            )
            self._preflight_done = True
            return
        with trace_span("preflight", "runner", "lifecycle"):
            report = verify_pipeline(self.model, data)
        for issue in report.issues:
            self._logger.info(f"pre-flight: {issue.format()}")
        # done only on success: a rejected plan must be re-verified on a
        # retried train() even when the caller fixed it without going
        # through rearm_preflight
        report.raise_if_failed()
        self._preflight_done = True
        self._logger.info(f"pre-flight: {report.summary()}")

    # --- live observability (LiveMetricsMixin provides the wiring) ----------
    def _health_snapshot(self) -> Dict:
        return dict(
            status="aborted" if getattr(self, "aborted", False) else "ok",
            epoch=self._epoch,
            iter=self._iter,
            max_iters=self._max_iters,
        )

    # --- hooks --------------------------------------------------------------
    def register_hook(self, hook: Hook) -> None:
        assert isinstance(hook, Hook)
        self._hooks.append(hook)

    def _call_hook(self, fn_name: str) -> None:
        for hook in self._hooks:
            getattr(hook, fn_name)(self)

    # --- training -----------------------------------------------------------
    def train(self, data_loader) -> None:
        self.data_loader = data_loader
        self.model.train(True)
        self.aborted = False
        self._call_hook("before_run")
        try:
            self._train_loop(data_loader)
        except Exception:
            # a training *error* (NanGuardHook action="raise", data
            # corruption) marks the live params suspect so CheckpointHook
            # skips its final save; KeyboardInterrupt is deliberately NOT
            # Exception — a user interrupt's params are fine and the
            # partial-epoch save should still happen
            self.aborted = True
            raise
        finally:
            # after_run must fire even when training raises: hooks flush
            # files, close handles, clean timers
            self._call_hook("after_run")

    def _train_loop(self, data_loader) -> None:
        while self._epoch < self._max_epochs and not self._stop:
            self._call_hook("before_train_epoch")
            self._inner_iter = 0
            exhausted = True

            batches = iter(data_loader)
            while True:
                # the sinks are looked up once per iteration: a profiler
                # that starts mid-iteration is seen from the next one on
                sp = span_sinks()
                lane = sp.lane("runner", "loop")
                with sp.span("sky.runner.iter", lane, {"iter": self._iter}):
                    with sp.span("sky.runner.data", lane):
                        batch = next(batches, _EXHAUSTED)
                    if batch is _EXHAUSTED:
                        break
                    if self._iter >= self._max_iters or self._stop:
                        exhausted = False
                        break
                    self._train_iter(sp, lane, *batch)

            if not exhausted:
                # max_iters / stop interrupted the epoch mid-stream: the
                # epoch did NOT complete, so don't count it and don't fire
                # after_train_epoch (a CheckpointHook there would label a
                # partial epoch as finished and a resume would skip the
                # rest of its data)
                break
            self._epoch += 1
            self._call_hook("after_train_epoch")
            if self._iter >= self._max_iters:
                break

    def _train_iter(self, sp, lane, data, labels) -> None:
        """One iteration under ``sky.runner.iter``: log line, pre-flight,
        hooks, the step, the step's stats and log line, hooks."""
        with sp.span("sky.runner.log", lane):
            self._logger.info(f"epoch: {self._epoch}, iter: {self._iter}")
        self.current_batch = (data, labels)
        self._preflight(data)
        with sp.span("sky.runner.hooks", lane,
                     {"point": "before_train_iter"}):
            self._call_hook("before_train_iter")

        with sp.span("sky.runner.rng", lane):
            self._rng, step_rng = jax.random.split(self._rng)
        with sp.span("sky.runner.timer", lane):
            self._timer.add_timestamp()
        loss = self.model.train_step(data, labels, rng=step_rng)
        with sp.span("sky.runner.timer", lane):
            self._timer.add_timestamp()

        with sp.span("sky.runner.log", lane):
            stats = self.model.stats
            self.phase_timer.record("forward", stats.forward_s)
            self.phase_timer.record("backward", stats.backward_s)
            self.phase_timer.record("step", stats.step_s)
            self.phase_timer.record("dispatch", stats.dispatch_s)
            overhead = (
                f" | dispatch: {stats.dispatch_s:.4f} "
                f"(copies {stats.transfers}, elided "
                f"{stats.transfers_elided}, compiles {stats.compiles})"
            )
            if stats.interleaved:
                self._logger.info(
                    f"loss: {loss:.6f} | fwd+bwd (fused, 1f1b): "
                    f"{stats.forward_s:.4f} | step time: "
                    f"{stats.step_s:.4f}{overhead}"
                )
            else:
                self._logger.info(
                    f"loss: {loss:.6f} | forward time: "
                    f"{stats.forward_s:.4f} | backward time: "
                    f"{stats.backward_s:.4f} | step time: "
                    f"{stats.step_s:.4f}{overhead}"
                )

        self._iter += 1
        self._inner_iter += 1
        if self.timeseries is not None:
            self.timeseries.sample()
        with sp.span("sky.runner.hooks", lane,
                     {"point": "after_train_iter"}):
            self._call_hook("after_train_iter")

    # --- evaluation ----------------------------------------------------------
    def evaluate(
        self,
        data_loader,
        max_batches: Optional[int] = None,
        task: Optional[str] = None,
    ) -> Dict:
        """Eval pass: mean loss + accuracy over a dataloader.

        Runs the pipeline forward in eval mode (no dropout rngs) with the
        ``val`` hook lifecycle.  ``task`` adds the GLUE task's own metrics
        (F1 for mrpc, Matthews for cola, ...) computed over all predictions.
        The reference has no eval loop at all — its runner only trains —
        so this is capability the decomposed model zoo makes free.
        """
        import numpy as np

        if task is not None:
            from ..ops.metrics import TASK_METRICS

            if task.lower() not in TASK_METRICS:
                raise ValueError(
                    f"unknown task {task!r}; known: {sorted(TASK_METRICS)}"
                )

        self.model.train(False)
        self._call_hook("before_val_epoch")
        loss_sum = 0.0
        correct = 0
        num_predictions = 0
        num_examples = 0
        all_preds = [] if task is not None else None
        all_labels = [] if task is not None else None
        for i, (data, labels) in enumerate(data_loader):
            if max_batches is not None and i >= max_batches:
                break
            self._call_hook("before_val_iter")
            logits = self.model.forward(data)  # stays on device for the loss
            labels = np.asarray(labels)
            batch_loss = float(
                self.model._loss_fn(logits, jax.numpy.asarray(labels))
            )
            n = len(labels)
            # per-example weighting: a ragged final batch must not count
            # its examples more than full batches do
            loss_sum += batch_loss * n
            logits_host = np.asarray(logits)
            if logits_host.ndim == 3:
                if task is not None:
                    raise ValueError(
                        "task metrics need per-example classification "
                        "logits; got token-level logits "
                        f"{logits_host.shape}"
                    )
                # token-level (causal LM): the logit at position t predicts
                # token t+1, so compare shifted
                preds = logits_host.argmax(axis=-1)[:, :-1]
                targets = labels[:, 1:]
                correct += int((preds == targets).sum())
                num_predictions += targets.size
            else:
                preds = logits_host.argmax(axis=-1)
                correct += int((preds == labels).sum())
                num_predictions += n
                if all_preds is not None:
                    all_preds.append(preds)
                    all_labels.append(labels)
            num_examples += n
            self._call_hook("after_val_iter")
        self._call_hook("after_val_epoch")
        self.model.train(True)
        result = {
            "loss": loss_sum / num_examples if num_examples else float("nan"),
            "accuracy": (
                correct / num_predictions if num_predictions else float("nan")
            ),
            "num_examples": num_examples,
        }
        if all_preds:
            from ..ops.metrics import compute_task_metrics

            task_metrics = compute_task_metrics(
                task, np.concatenate(all_preds), np.concatenate(all_labels)
            )
            # accuracy is already computed incrementally above
            result.update(
                {k: v for k, v in task_metrics.items() if k not in result}
            )
        return result


__all__ = ["Runner"]
