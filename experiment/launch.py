#!/usr/bin/env python
"""Single-controller experiment launcher.

TPU-native replacement for the reference launcher
(``/root/reference/experiment/launch.py:20-235``).  The reference needed
Slurm ranks, a HOST rendezvous file, and an RPC world where rank 0
orchestrates passive workers; under single-controller JAX one process owns
all devices, so the launcher is just: load config -> build worker pool +
parameter server + dataloader -> profile + allocate -> build the pipeline ->
train.  Allocation failure degrades to a clean exit without training
(parity with ``launch.py:117-145``).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np
import optax

from skycomputing_tpu import load_config
from skycomputing_tpu.builder import build_data_generator, build_dataloader_from_cfg, build_hook
from skycomputing_tpu.dynamics import (
    Allocator,
    DeviceBenchmarker,
    ModelBenchmarker,
    ParameterServer,
    WorkerManager,
)
from skycomputing_tpu.ops import build_loss
from skycomputing_tpu.parallel import PipelineModel
from skycomputing_tpu.runner import Runner
from skycomputing_tpu.stimulator import Stimulator
from skycomputing_tpu.telemetry import trace_span
from skycomputing_tpu.utils import (
    Logger,
    enable_persistent_compilation_cache,
)


def build_optimizer(optim_cfg: dict):
    cfg = dict(optim_cfg)
    name = cfg.pop("optim_type").lower()
    return getattr(optax, name)(**cfg)


def _phase(name: str, seconds: dict):
    """One set-up phase: a ``sky.launch.<name>`` span (in the ring and in
    a profiler trace when either is on) whose seconds land in
    ``seconds`` for the launcher's own log line, always."""
    return trace_span(f"sky.launch.{name}", "launch", "setup",
                      seconds_into=seconds)


#: positions of a token table the parameter server's probe keeps
_INIT_PROBE_POSITIONS = 128


def run(cfg, logger: Logger) -> int:
    phases: dict = {}
    devices = jax.devices()
    logger.info(
        f"devices: {len(devices)} x {devices[0].platform} "
        f"({devices[0].device_kind})"
    )
    # before the profilers' first compile, not first at Runner build
    logger.info(
        f"compile cache: {enable_persistent_compilation_cache()}"
    )

    # --- cluster membership -------------------------------------------------
    worker_manager = WorkerManager()
    worker_manager.load_worker_pool_from_config(cfg.worker_config)

    # --- data ---------------------------------------------------------------
    with _phase("data", phases):
        data_loader = build_dataloader_from_cfg(cfg.data_config)

    def batches():
        for data, labels in data_loader:
            if len(data) == 3:
                # GlueDataset rows are ((ids, mask, segs), label);
                # BertEmbeddings takes (ids, token_type_ids, attention_mask)
                ids, mask, segs = data
                yield (ids, segs, mask), labels
            else:
                yield data, labels

    class BatchAdapter:
        def __len__(self):
            return len(data_loader)

        def __iter__(self):
            return batches()

    # --- parameter server (host copy of the full model) ---------------------
    with _phase("parameter_server", phases):
        # parameters depend on no batch size and on no count of positions:
        # initialising on the host runs one row through, and of a table of
        # token ids ([rows, positions] integers) its first positions
        probe = tuple(
            x[:1, :_INIT_PROBE_POSITIONS]
            if x.ndim == 2 and np.issubdtype(x.dtype, np.integer) else x[:1]
            for x in map(np.asarray, next(iter(BatchAdapter()))[0])
        )
        parameter_server = ParameterServer(
            cfg.model_config, example_inputs=probe, rng=jax.random.key(0)
        )
    logger.info(f"parameter server: {parameter_server.num_layers} layers")

    # --- profiling + allocation ---------------------------------------------
    # (the profilers are built here and run inside the allocator: their
    # ``allocator.profiles`` / ``bench.device`` / ``bench.model`` spans
    # nest under ``sky.launch.allocate``)
    optimizer = build_optimizer(cfg.train_config["optim_cfg"])
    with _phase("profile", phases):
        bench_cfg = cfg.allocator_config["benchmark_config"]
        model_bench = ModelBenchmarker(
            cfg.model_config,
            build_data_generator(**bench_cfg["model"]["data_generator_cfg"]),
            param_scale=bench_cfg["model"].get("param_scale", 2),
            # measured seconds a layer instead of XLA's FLOP count: what
            # layers of unequal kind need (a scan and a matrix product of
            # equal FLOPs are not equally long); "programs" times the
            # stage programs themselves and needs the job's optimizer
            timed=bench_cfg["model"].get("timed", False),
            optimizer=optimizer,
        )
        stimulator = (
            Stimulator(worker_manager.size)
            if os.getenv("STIMULATE") is not None
            else None
        )
        device_bench = DeviceBenchmarker(
            worker_manager,
            build_data_generator(
                **bench_cfg["device"]["data_generator_cfg"]),
            bench_cfg["device"]["model_config"],
            iterations=bench_cfg["device"].get("iterations", 10),
            devices=devices,
            stimulator=stimulator,
        )
        allocator = Allocator(
            cfg.model_config, worker_manager, model_bench, device_bench,
            logger=logger,
        )

    allocate_type = cfg.allocator_config["type"]
    logger.info(f"allocation strategy: {allocate_type}")
    try:
        with _phase("allocate", phases):
            if allocate_type == "optimal":
                allocator.optimal_allocate()
            elif allocate_type == "dynamic":
                allocator.dynamic_allocate()
            elif allocate_type == "even":
                allocator.even_allocate()
            else:
                raise ValueError(f"unknown ALLOCATE_TYPE {allocate_type!r}")
    except Exception as exc:  # allocation failure -> clean exit, no training
        logger.info(f"allocation failed: {exc!r} — skipping training")
        return 1

    for worker in worker_manager.worker_pool:
        logger.info(
            f"  stage rank={worker.rank} name={worker.name} "
            f"device={worker.device_index} layers={len(worker.model_config)}"
        )

    # --- pipeline + runner ---------------------------------------------------
    with _phase("build_pipeline", phases):
        model = PipelineModel(
            worker_manager,
            parameter_server,
            optimizer,
            build_loss(cfg.train_config["loss_cfg"]),
            devices=devices,
            num_microbatches=getattr(cfg, "NUM_MICROBATCHES", 1),
            schedule=getattr(cfg, "SCHEDULE", "gpipe"),
        )

        runner = Runner(
            model,
            parameter_server,
            worker_manager,
            max_epochs=cfg.train_config["runner_cfg"]["max_epochs"],
            max_iters=cfg.train_config["runner_cfg"]["max_iters"],
            timer_cfg=cfg.train_config.get("timer_config"),
            logging_cfg=cfg.logging_config,
        )
        for hook_cfg in cfg.train_config.get("hook_config", []):
            runner.register_hook(build_hook(hook_cfg))
    setup = {k.rsplit(".", 1)[1]: round(v, 3) for k, v in phases.items()}
    logger.info(f"set-up phases (s): {setup}")

    runner.train(BatchAdapter())
    summary = runner.phase_timer.summary()
    logger.info(f"phase means (s): {summary}")
    logger.info("training complete")
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description="skycomputing-tpu launcher")
    parser.add_argument("-c", "--config", required=True, help="config .py path")
    parser.add_argument(
        "--allocate-type",
        choices=["even", "optimal", "dynamic"],
        help="override ALLOCATE_TYPE from the config",
    )
    args = parser.parse_args()

    if args.allocate_type:
        os.environ["SKYTPU_ALLOCATE_TYPE"] = args.allocate_type

    cfg = load_config(args.config)
    if args.allocate_type:
        cfg.allocator_config["type"] = args.allocate_type

    logger = Logger(**cfg.logging_config) if "logging_config" in cfg else Logger()
    return run(cfg, logger)


if __name__ == "__main__":
    sys.exit(main())
