"""Device + model benchmarkers.

TPU-native re-design of ``scaelum/dynamics/benchmarker.py``:

- ``DeviceBenchmarker`` (reference :30-133) measured each RPC worker's speed
  by fanning out ``rpc_async`` calls; here every device hangs off the single
  controller, so the fan-out is a loop of timed jit executions committed to
  each device, with available memory read from ``device.memory_stats()``
  (the ``nvidia-smi`` analog) or per-worker ``mem_limit`` config.
- ``ModelBenchmarker`` (reference :136-201) measured per-layer FLOPs/memory
  by *running* each layer, with a hard-coded BERT shortcut to avoid OOM;
  here profiling is fully static (XLA cost analysis over abstract shapes —
  see ``Estimator.benchmark_model``) and the shortcut generalizes to
  config-hash dedup: identical (layer-config, input-shape) pairs are
  compiled once regardless of model family.
- Stimulator distortion matches the reference hook (:126-129): compute time
  is multiplied and available memory divided by per-worker factors, enabled
  by the ``STIMULATE`` env var or an explicit ``stimulator=`` argument.
"""

from __future__ import annotations

import abc
import json
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..builder import build_layer, build_layer_stack
from ..dataset import BaseGenerator
from ..stimulator import Stimulator
from ..telemetry import trace_span
from ..utils import generate_worker_name
from .estimator import Estimator
from .worker_manager import WorkerManager


class BaseBenchmarker(abc.ABC):
    @abc.abstractmethod
    def benchmark(self):
        ...


def _device_for(worker, devices):
    return devices[worker.device_index % len(devices)]


def device_available_memory_mb(device, fallback_fraction: float = 0.8) -> float:
    """Free device memory in MB, as the device itself reports it.

    The CPU backend's virtual devices have no memory of their own and
    report nothing, so they share the host's available RAM.  Any other
    device that reports nothing is an error: planning a TPU's stages
    against the host's RAM would look like a working allocation.
    """
    stats = device.memory_stats()
    if stats and "bytes_limit" in stats:
        free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
        return free / 1024.0**2
    if device.platform != "cpu":
        raise RuntimeError(
            f"{device} ({device.device_kind}) reports no memory_stats(); "
            f"set the worker's mem_limit or fix the runtime — its memory "
            f"is not the host's"
        )
    import psutil

    return psutil.virtual_memory().available * fallback_fraction / 1024.0**2


class DeviceBenchmarker(BaseBenchmarker):
    def __init__(
        self,
        worker_manager: WorkerManager,
        data_generator: BaseGenerator,
        model_config: List[Dict],
        iterations: int = 30,
        dtype: Optional[str] = None,
        devices: Optional[Sequence[Any]] = None,
        stimulator: Optional[Stimulator] = None,
    ):
        self._worker_manager = worker_manager
        self._model_config = model_config
        self._data_generator = data_generator
        self._iterations = iterations
        self._dtype = dtype
        self._devices = list(devices) if devices is not None else jax.devices()
        if stimulator is None and os.getenv("STIMULATE") is not None:
            stimulator = Stimulator(worker_manager.size)
        self._stimulator = stimulator
        # raw per-worker measurements memoized by worker identity: the
        # refine_allocation closed loop re-enters benchmark() once per
        # re-solve, and re-timing unchanged devices only repeats compile +
        # execute work and injects fresh noise (keyed by worker.id, not
        # rank — allocation re-ranks the pool)
        self._measure_cache: Dict[str, Tuple[float, float]] = {}
        # raw SPEED measurements deduped by physical device: in the
        # single-controller world, workers mapped onto the same device
        # are the same hardware — re-timing the identical jitted proxy
        # per worker (64x at headline scale) repeats wall clock and, far
        # worse, injects per-worker noise that fakes heterogeneity the
        # solver then chases: exactly-equal raw times keep the profiled
        # device_time collapsed into its true slowdown classes, which is
        # what lets the class-exact solver certify the allocation.
        # Emulated heterogeneity (stimulator, slowdown config) applies
        # AFTER this cache, per worker, unchanged.
        self._device_time_cache: Dict[Any, float] = {}

    def local_benchmark(self, worker, data) -> Tuple[float, float]:
        """Time the proxy model on one worker's device; probe free memory."""
        device = _device_for(worker, self._devices)
        if device in self._device_time_cache:
            elapsed = self._device_time_cache[device]
        else:
            with trace_span("bench.device", "dynamics", "benchmark",
                            {"device": str(device)}):
                elapsed = self._measure_device(device, data)
            self._device_time_cache[device] = elapsed

        mem_limit = worker.extra_config.get("mem_limit", -1)
        if mem_limit and mem_limit > 0:
            avai_mem = float(mem_limit)
        else:
            avai_mem = device_available_memory_mb(device)
        return elapsed, avai_mem

    def _measure_device(self, device, data) -> float:
        """One timed proxy-model run on ``device`` (the cache-miss path)."""
        stack = build_layer_stack(self._model_config)
        data = data if isinstance(data, tuple) else (data,)
        if self._dtype is not None:
            data = tuple(np.asarray(d).astype(self._dtype) for d in data)

        params = stack.init(jax.random.key(0), *data)
        params = jax.device_put(params, device)

        def fwd(p, *xs):
            return stack.apply(p, *xs)

        return Estimator.benchmark_speed(
            fwd,
            [params, *data],
            device=device,
            iterations=self._iterations,
        )

    def benchmark(self) -> Dict[str, Dict[str, float]]:
        results: Dict[str, Dict[str, float]] = {}
        data = None

        for worker in self._worker_manager.worker_pool:
            worker_name = generate_worker_name(worker.rank)
            if worker.id not in self._measure_cache:
                if data is None:
                    data = self._data_generator.generate()
                self._measure_cache[worker.id] = self.local_benchmark(
                    worker, data
                )
            elapsed, avai_mem = self._measure_cache[worker.id]

            if self._stimulator is not None:
                # keyed by the worker's STABLE index, not current rank:
                # allocation re-ranks the pool, and a post-allocation
                # re-benchmark (the refine_allocation closed loop) must
                # see the same per-worker heterogeneity as the first pass
                elapsed *= self._stimulator.compute_slowdown(worker.stim_index)
                avai_mem /= self._stimulator.memory_slowdown(worker.stim_index)

            results[worker_name] = dict(time=elapsed, avai_mem=avai_mem)
        return results


def _layer_key(layer_cfg: Dict, input_avals) -> str:
    """What two layers must share to be profiled once: the kind (the
    registered ``layer_type``), the rest of the config whatever the order
    of its keys, and the shapes that come in.  The kind stands first and
    apart, so two layers of different kinds never collide, equal though
    their options may be."""
    cfg = dict(layer_cfg)
    kind = cfg.pop("layer_type", None)
    shapes = [(tuple(a.shape), str(a.dtype)) for a in input_avals]
    return json.dumps([kind, cfg, shapes], sort_keys=True, default=str)


class ModelBenchmarker(BaseBenchmarker):
    """Per-layer cost + memory profile over the full model config.

    Two profiling modes:

    - static (default): XLA cost-analysis FLOPs over abstract shapes —
      no params materialized, no FLOPs executed (how a 160-layer model
      profiles without OOM; generalizes the reference's hard-coded BERT
      shortcut, ``scaelum/dynamics/benchmarker.py:163-166``);
    - ``timed=True``: per-layer *measured* forward+backward seconds
      (real params, jitted, warmed, chained iterations), threading each
      layer's real outputs into the next layer's inputs exactly like the
      reference's running profiler (``benchmarker.py:156-201``).  Static
      FLOPs mis-rank memory-bound layers (attention thirds) against
      matmul-bound ones (FFN thirds), which costs the allocator real
      bottleneck quality — the headline bench profiles timed.

    Both modes dedup by (layer-config, input-shape) hash, so deep stacked
    models compile/measure each distinct unit once.
    """

    def __init__(
        self,
        model_config: List[Dict],
        data_generator: BaseGenerator,
        dtype: Optional[str] = None,
        param_scale: int = 2,
        device: Optional[str] = None,  # accepted for config parity; unused
        timed: bool = False,
        timed_iterations: int = 8,
        optimizer: Any = None,
    ):
        self._model_config = model_config
        self._data_generator = data_generator
        self._dtype = dtype
        self._param_scale = param_scale
        # ``timed="programs"``: time the very programs a pipeline stage
        # will run for the layer (``StageRuntime`` of one layer, built
        # with the job's ``optimizer`` so that the engine finds the same
        # programs in its cache): nothing is compiled for the profile
        # alone
        self._stage_programs = timed == "programs"
        self._optimizer = optimizer
        if self._stage_programs and optimizer is None:
            raise ValueError('timed="programs" needs the job\'s optimizer')
        self._timed = bool(timed)
        self._timed_iterations = int(timed_iterations)
        self._result: Optional[Tuple[List[float], List[float]]] = None

    @property
    def model_config(self) -> List[Dict]:
        return self._model_config

    def benchmark(self) -> Tuple[List[float], List[float]]:
        """Per-layer (cost, mem_MB) lists over the full model config.

        ``cost`` is XLA FLOPs in static mode, measured fwd+bwd seconds in
        timed mode — the allocator only consumes relative magnitudes, so
        the two are drop-in interchangeable.  The result is memoized: the
        profile is deterministic given (config, generator), and in timed
        mode re-measuring on every allocator call would repeat real
        compile+execute work.
        """
        if self._result is not None:
            return self._result
        with trace_span(
            "bench.model", "dynamics", "benchmark",
            {"layers": len(self._model_config), "timed": self._timed},
        ):
            self._result = self._benchmark()
        return self._result

    def _time_stage_programs(self, layer_cfg, module, inputs, first: bool):
        """(outputs, seconds of one forward + one backward program) of the
        layer as a one-layer pipeline stage runs it: the engine's own
        programs, donation and recomputation included."""
        import time

        import jax.numpy as jnp

        from ..parallel.pipeline import StageRuntime

        inputs = tuple(jnp.asarray(x) for x in inputs)
        k_params, k_dropout = jax.random.split(jax.random.key(0))
        params = jax.jit(lambda key: module.init(
            {"params": key, "dropout": k_dropout}, *inputs)["params"]
        )(k_params)
        stage = StageRuntime(0, [layer_cfg], [params], jax.devices()[0],
                             self._optimizer, differentiable_inputs=not first)
        rng = jax.random.key(1)
        outputs = stage.forward_placed(inputs, rng)
        dy = jax.tree_util.tree_map(jnp.ones_like, outputs)
        fresh = lambda: jax.tree_util.tree_map(jnp.copy, inputs)

        def once():
            out = stage.forward_placed(inputs, rng)
            grads, _ = stage.backward(fresh(), rng, dy)  # donates its input
            return out, grads

        jax.block_until_ready(once())
        best = float("inf")
        for _ in range(3):
            start = time.perf_counter()
            for _ in range(self._timed_iterations):
                result = once()
            jax.block_until_ready(result)
            best = min(best, (time.perf_counter() - start)
                       / self._timed_iterations)
        return outputs, best

    def _benchmark(self) -> Tuple[List[float], List[float]]:
        data = self._data_generator.generate()
        data = data if isinstance(data, tuple) else (data,)

        cost_list: List[float] = []
        mem_list: List[float] = []
        cache: Dict[str, Tuple[Any, float, float]] = {}

        if self._timed:
            current = data
            for layer_cfg in self._model_config:
                avals = tuple(
                    jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
                    for x in jax.tree_util.tree_leaves(current)
                )
                key = _layer_key(layer_cfg, avals)
                if key in cache:
                    outputs, seconds, mem = cache[key]
                else:
                    cfg = dict(layer_cfg)
                    layer_type = cfg.pop("layer_type")
                    module = build_layer(layer_type, **cfg)
                    if self._stage_programs:
                        outputs, seconds = self._time_stage_programs(
                            layer_cfg, module, current,
                            first=not cost_list,
                        )
                    else:
                        outputs, seconds = Estimator.benchmark_train_time(
                            module, current,
                            iterations=self._timed_iterations,
                        )
                    # memory stays the static formula so the allocator's
                    # capacity model is identical across modes (no FLOPs
                    # compile — the cost here is the measured seconds)
                    _, mem = Estimator.estimate_memory(
                        module, avals, param_scale=self._param_scale
                    )
                    cache[key] = (outputs, seconds, mem)
                cost_list.append(seconds)
                mem_list.append(mem)
                out = outputs if isinstance(outputs, tuple) else (outputs,)
                current = tuple(jax.tree_util.tree_leaves(out))
            return cost_list, mem_list

        avals = tuple(
            jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype) for x in data
        )
        for layer_cfg in self._model_config:
            key = _layer_key(layer_cfg, avals)
            if key in cache:
                out_aval, flops, mem = cache[key]
            else:
                cfg = dict(layer_cfg)
                layer_type = cfg.pop("layer_type")
                module = build_layer(layer_type, **cfg)
                out_aval, flops, mem = Estimator.benchmark_model(
                    module, avals, param_scale=self._param_scale
                )
                cache[key] = (out_aval, flops, mem)
            cost_list.append(flops)
            mem_list.append(mem)
            out = out_aval if isinstance(out_aval, tuple) else (out_aval,)
            avals = tuple(
                jax.ShapeDtypeStruct(a.shape, a.dtype)
                for a in jax.tree_util.tree_leaves(out)
            )

        return cost_list, mem_list


__all__ = [
    "BaseBenchmarker",
    "DeviceBenchmarker",
    "ModelBenchmarker",
    "device_available_memory_mb",
]
