"""Host-driven MPMD pipeline engine.

This replaces the reference's entire RPC execution core — ``RpcModel``
(``scaelum/model/rpc_model.py:16-63``), ``LocalModule``/``RemoteModule``
(``rpc_module.py:45-99``), ``ModuleWrapper`` (``builder/module_wrapper.py``),
torch distributed autograd and ``DistributedOptimizer``
(``runner/runner.py:127-139``) — with a single-controller JAX design:

- each pipeline **stage** is a contiguous layer slice compiled into three
  jitted programs (forward / backward / optimizer-update) whose parameters
  and optimizer state are committed to that stage's device;
- **activation handoff** is ``jax.device_put`` between devices — XLA moves
  the buffers over ICI without host round-trips, and async dispatch lets
  stage k+1's transfer overlap stage k's compute;
- **backward** needs no distributed autograd engine: each stage's backward
  program rematerializes its forward (jax.vjp inside jit) and returns
  (param-grads, input-cotangents); the host threads cotangents backwards
  exactly like the reference's autograd context did, but compiled;
- **microbatching** (absent in the reference — its batches traverse stages
  strictly sequentially) is a first-class knob: GPipe-style fill-drain with
  gradient accumulation, giving real overlap across devices from async
  dispatch alone;
- the reference's per-worker **slowdown** emulation
  (``module_wrapper.py:109-140``: sleep proportional to measured forward
  time) is reproduced host-side for heterogeneity experiments on
  homogeneous slices.

Params stay float32 on device; compute dtype is whatever the layer modules
choose (bfloat16 by default for MXU-friendly matmuls).
"""

from __future__ import annotations

import os
import re
import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..builder import as_tuple, build_layer_stack
from ..dynamics.parameter_server import ParameterServer
from ..dynamics.worker_manager import WorkerManager
from ..telemetry import get_tracer, span_sinks


# --- hot-path switches & counters -------------------------------------------
# SKYTPU_HOTPATH=0 restores the legacy dispatch path (unconditional
# device_put, per-microbatch zero cotangents, no donation outside `update`,
# no input prefetch).  The A/B switch exists so tools/bench_step_overhead.py
# can measure the host-dispatch split of both paths in one report; the
# optimized path is the default and the one CI exercises.
HOTPATH = os.environ.get("SKYTPU_HOTPATH", "1") != "0"

# the ring lane of the step's host-side spans (``sky.pipe.*``: the issue
# loops, the barriers); per-stage ``fwd``/``bwd``/``update`` spans keep
# their own ``stage k`` lanes
_HOST_LANE = ("host", "dispatch")

# Backward/accumulate donation is an accelerator optimization: on TPU/GPU
# it cuts peak HBM (dead stage inputs and grad totals are reused in
# place), but on the CPU backend buffers are host RAM — there is nothing
# to save, and the donate bookkeeping measurably SLOWS dispatch (~12% per
# step on the 8-fake-device microbench).  So donation follows the
# backend, decided lazily at first program build (jax.default_backend()
# initializes the platform; import time is too early).  SKYTPU_DONATE=1/0
# forces it either way — tests use =1 to exercise the donated programs on
# CPU.  `update` keeps its historical unconditional donation.
_DONATE = [None]


def _donation_enabled() -> bool:
    if _DONATE[0] is None:
        forced = os.environ.get("SKYTPU_DONATE")
        if forced is not None:
            _DONATE[0] = forced != "0"
        elif not HOTPATH:
            _DONATE[0] = False
        else:
            _DONATE[0] = jax.default_backend() != "cpu"
    return _DONATE[0]


# A donated stage-input tuple includes integer leaves (token ids,
# attention masks) that have no cotangent and so can never alias into a
# gradient output; XLA warns about them once per lowered program.  That
# is expected and not actionable — the float activation buffers DO alias
# — so silence exactly that message.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)

# Process-global transfer accounting for the elided device_put below:
# "copies" counts puts that actually moved bytes (host->device or
# cross-device), "elided" counts same-device puts skipped entirely.
# Module-global like the program cache; snapshot-and-diff per step.
_TRANSFER_STATS = {"copies": 0, "elided": 0}

# Program-cache accounting (get_stage_programs): a miss means a full
# _StagePrograms build — layer-stack construction plus, on first execution,
# XLA compiles for fwd/bwd/update.
_PROGRAM_CACHE_STATS = {"hits": 0, "misses": 0}

# Host-dispatch accounting: how many times per step the Python issue
# loops call INTO jax — one count per jitted-program invocation
# ("programs": fwd/bwd/accumulate/update/loss/rng-fold) and one per
# device_put call that actually moves buffers ("puts"; elided puts are
# already tracked in _TRANSFER_STATS).  This is the figure the mesh-
# native engine collapses: a per-device loop pays O(devices) of these
# per microbatch tick, a mesh-native drive O(stages).  Snapshot-and-diff
# per step like the transfer counters.
_DISPATCH_STATS = {"programs": 0, "puts": 0}

# XLA backend-compile counter, fed by jax.monitoring: every executable the
# backend actually compiles (a jit cache miss that wasn't served by the
# persistent compilation cache).  This is the ground truth for "did this
# step recompile anything".  jax wraps the persistent-cache lookup AND the
# compile in one duration event, so a miss that the cache serves emits the
# event too; the cache's own hit event, which comes first, cancels it.
_XLA_COMPILES = [0]
_COMPILE_LISTENER = [False]
_SERVED_FROM_CACHE = [False]


def _ensure_compile_listener() -> None:
    if _COMPILE_LISTENER[0]:
        return
    _COMPILE_LISTENER[0] = True
    from jax import monitoring

    def _on_event(name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            _SERVED_FROM_CACHE[0] = True

    def _on_duration(name: str, _secs: float, **_kw) -> None:
        if name != "/jax/core/compile/backend_compile_duration":
            return
        if _SERVED_FROM_CACHE[0]:
            _SERVED_FROM_CACHE[0] = False
            return
        _XLA_COMPILES[0] += 1
        tracer = get_tracer()
        if tracer is not None:
            # the probe reports AFTER the compile finished: back
            # the start off the duration so the span sits where
            # the compile actually ran on the timeline
            end = tracer.now()
            tracer.complete(
                "xla_compile", tracer.lane("xla", "compile"),
                max(end - _secs * 1e6, 0.0), dur_us=_secs * 1e6,
            )

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def xla_compile_count() -> int:
    """Cumulative XLA backend compiles observed in this process."""
    _ensure_compile_listener()
    return _XLA_COMPILES[0]


def hotpath_counters() -> Dict[str, int]:
    """Snapshot of the process-global hot-path counters."""
    return {
        "transfer_copies": _TRANSFER_STATS["copies"],
        "transfers_elided": _TRANSFER_STATS["elided"],
        "program_cache_hits": _PROGRAM_CACHE_STATS["hits"],
        "program_cache_misses": _PROGRAM_CACHE_STATS["misses"],
        "program_dispatches": _DISPATCH_STATS["programs"],
        "put_dispatches": _DISPATCH_STATS["puts"],
        "xla_compiles": xla_compile_count(),
    }


def _is_resident(x, target) -> bool:
    """Is ``x`` already committed to ``target`` (a Device or Sharding)?

    MPMD stages commit to concrete devices; mesh-native stages commit to
    a ``NamedSharding`` over their sub-mesh — residency there is sharding
    equality (same mesh devices, same spec), which is exactly the
    condition under which a put would be a no-op copy.
    """
    if not isinstance(x, jax.Array):
        return False
    if isinstance(target, jax.sharding.Sharding):
        if x.sharding == target:
            return True
        try:
            # program outputs carry rank-normalized specs (P('dp') vs
            # P('dp', None, ...)); equivalence, not equality, decides
            # whether a put would move bytes
            return x.sharding.is_equivalent_to(target, x.ndim)
        except Exception:
            return False
    return x.device is target


def device_put_elided(tree, device):
    """``jax.device_put`` that skips leaves already living on ``device``.

    The issue loops put every activation/cotangent on its stage's device
    before dispatch; when producer and consumer share a device (deep
    pipelines on few chips, replica-0 reductions) the put is pure host
    overhead — the buffer is already where it must be.  Eliding it also
    preserves buffer identity, which is what lets backward donation reuse
    the producer's allocation instead of copying first.

    ``device`` may be a concrete jax Device (MPMD stages) or a
    ``jax.sharding.Sharding`` (mesh-native stages hand off activations
    with a put-to-sharding); either way a moving put is ONE batched call.
    """
    if not HOTPATH:
        _DISPATCH_STATS["puts"] += 1
        return jax.device_put(tree, device)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    resident = [_is_resident(x, device) for x in leaves]
    if all(resident):
        # the steady-state fast path: no api call, no tree rebuild
        _TRANSFER_STATS["elided"] += len(leaves)
        tracer = get_tracer()
        if tracer is not None:
            tracer.instant(
                "transfer_elided", tracer.lane("transfers", str(device)),
                {"leaves": len(leaves)},
            )
        return tree
    to_move = [x for x, r in zip(leaves, resident) if not r]
    # ONE batched put for everything that actually moves: per-call fixed
    # overhead in jax.device_put dwarfs the per-leaf cost, so per-leaf
    # puts would give back most of what elision saves
    moved = iter(jax.device_put(to_move, device))
    _DISPATCH_STATS["puts"] += 1
    _TRANSFER_STATS["copies"] += len(to_move)
    _TRANSFER_STATS["elided"] += len(leaves) - len(to_move)
    tracer = get_tracer()
    if tracer is not None:
        tracer.instant(
            "transfer", tracer.lane("transfers", str(device)),
            {"moved": len(to_move), "elided": len(leaves) - len(to_move)},
        )
    out = [x if r else next(moved) for x, r in zip(leaves, resident)]
    return jax.tree_util.tree_unflatten(treedef, out)


# Jitted (base, m, k) -> key derivation.  Folding eagerly costs ~0.6 ms
# per key in bind/dispatch overhead and a step needs M x S keys; the
# compiled pair-fold is ~15 us per key with IDENTICAL threefry math, so
# seeded runs replay exactly the same masks as the eager path.
_fold2 = jax.jit(
    lambda rng, m, k: jax.random.fold_in(jax.random.fold_in(rng, m), k)
)
_fold1 = jax.jit(jax.random.fold_in)


def _step_rngs(rng, M: int, S: int):
    """The per-(microbatch, stage) dropout-key table for one step."""
    if HOTPATH:
        _DISPATCH_STATS["programs"] += M * S
        return [[_fold2(rng, m, k) for k in range(S)] for m in range(M)]
    return [
        [jax.random.fold_in(jax.random.fold_in(rng, m), k) for k in range(S)]
        for m in range(M)
    ]


def _avals(tree):
    """Shapes, dtypes and (where an array is committed to one) shardings of
    a tree of arrays, ``None`` leaves staying: what lowers a program to
    the module the call with the arrays themselves lowered it to, so that
    compiling it again is a cache hit and not a second entry."""
    def aval(a):
        committed = getattr(a, "committed", getattr(a, "_committed", False))
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if committed else None)

    return jax.tree_util.tree_map(aval, tree)


_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) .*op_name=\"([^\"]*)\"")


def scoped_instructions_of(hlo_text: str, scopes: Sequence[str]):
    """``[(instruction, result type, scope)]`` over the instructions of an
    optimized HLO module that carry an ``op_name``: ``scope`` is the first
    of ``scopes`` (each a ``jax.named_scope``; the backward pass keeps it
    inside ``transpose(jvp(...))``) found in it, or ``""``.  A device
    trace names its events by instruction, and by nothing else: this is
    the way back to the scope."""
    rows = []
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if found:
            name, result, op_name = found.groups()
            scope = next((s for s in scopes if s in op_name), "")
            rows.append((name, result, scope))
    return rows


def _is_last(path) -> bool:
    """Is the sown leaf at ``path`` one that holds the LATEST call's value
    (a name that starts with ``last_`` somewhere on the way to it) and not
    a running total?"""
    return any(str(getattr(k, "key", "")).startswith("last_") for k in path)


def fold_counters(totals, sown):
    """What a stage keeps of its layers' ``counters`` collection after one
    more forward: a leaf is ADDED to its running total, unless its name
    (or a name above it) starts with ``last_``: then the new value stands
    in the old one's place (what the last call chose, what state it ended
    in: read against a reference outside any timed window)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, total, new: new if _is_last(path) else total + new,
        totals, sown)


def _split_microbatches(tree, num_microbatches: int, what: str = "microbatches"):
    """Leading-axis split of every leaf into equal shards."""
    def split(x):
        x = np.asarray(x)
        if x.shape[0] % num_microbatches != 0:
            raise ValueError(
                f"batch size {x.shape[0]} not divisible by "
                f"{num_microbatches} {what}"
            )
        return x.reshape(num_microbatches, x.shape[0] // num_microbatches,
                         *x.shape[1:])
    stacked = jax.tree_util.tree_map(split, tree)
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    return [
        jax.tree_util.tree_unflatten(treedef, [leaf[m] for leaf in leaves])
        for m in range(num_microbatches)
    ]


# Stage programs keyed by (canonical layer-config json, optimizer identity).
# Deep pipelines repeat layer patterns, so many stages share a slice
# structure — e.g. a 160-unit BERT split 8 ways has only a handful of
# distinct slice shapes — and jit caches on function identity, which
# per-stage closures would defeat.  Sharing the compiled programs cuts
# compile counts severalfold for the MPMD engine and the benchmark.
#
# The cache is process-global, bounded LRU (PROGRAM_CACHE_MAX_ENTRIES
# slice structures; eviction releases the executables and the pinned
# optimizer object, whose id is part of the key and therefore cannot be
# recycled while cached).  clear_program_cache() still empties it
# explicitly.  Sharing across models requires passing the SAME optimizer
# object — two equal-hyperparameter optax objects have different ids and
# do not share (optax transforms expose no reliable value-hash to key on).
_PROGRAM_CACHE: Dict = {}
# Default 64.  The headline bench raises this to 256 via
# SKYTPU_PROGRAM_CACHE_MAX (its successive 64-stage allocations exceed 64
# distinct slice structures, and re-compiles dominated its wall clock) —
# but a LARGER default is hostile to long-lived many-model processes:
# each entry pins jitted executables (mapped code pages), and a full
# test-suite process at cap 256 accumulated enough mappings to segfault
# XLA's compiler ~50 min in (r05; cap 64 had always been stable).
PROGRAM_CACHE_MAX_ENTRIES = max(
    1, int(os.environ.get("SKYTPU_PROGRAM_CACHE_MAX", "64"))
)


def clear_program_cache() -> None:
    """Release all cached stage programs (compiled executables)."""
    _PROGRAM_CACHE.clear()


class _StagePrograms:
    """The jitted fwd/bwd/update programs for one layer-slice structure."""

    def __init__(self, layer_cfgs, optimizer):
        self.stack = build_layer_stack(layer_cfgs)
        # eval twin: same params, dropout forced off (for configs that
        # carry a `deterministic` knob); used when forward gets no rng
        self.eval_stack = build_layer_stack(
            [
                {**cfg, "deterministic": True} if "deterministic" in cfg
                else cfg
                for cfg in layer_cfgs
            ]
        )
        # pinned: the cache key uses id(optimizer), which is only sound
        # while this strong reference keeps the id from being recycled —
        # declared in the skyaudit MANIFEST id_key_pins (skydet DET004)
        # and regression-guarded by
        # tests/test_determinism_lint.py::test_optimizer_id_key_is_pinned
        self.optimizer = optimizer
        stack, eval_stack = self.stack, self.eval_stack

        def fwd(params, inputs, rng):
            if rng is None:
                return as_tuple(eval_stack.apply(params, *inputs))
            return as_tuple(stack.apply(params, *inputs, dropout_rng=rng))

        # layers that sow counters (an expert layer's tokens by expert):
        # a forward twin that takes the stage's running totals and returns
        # them with this call's added, so they never leave the device
        self.has_counters = any(
            getattr(m, "has_counters", False) for m in stack.modules
        )

        def fwd_new_counters(params, inputs, rng):
            which = eval_stack if rng is None else stack
            out, sown = which.apply(params, *inputs, dropout_rng=rng,
                                    counters=True)
            return as_tuple(out), sown

        def fwd_counted(params, inputs, rng, totals):
            out, sown = fwd_new_counters(params, inputs, rng)
            return out, fold_counters(totals, sown)

        def bwd(params, inputs, rng, dy):
            # Rematerialize forward inside backward: trades FLOPs for HBM —
            # activations never persist between fwd and bwd passes.
            def f(p, x):
                return as_tuple(stack.apply(p, *x, dropout_rng=rng))

            _, vjp_fn = jax.vjp(f, params, inputs)
            dparams, dx = vjp_fn(dy)
            return dparams, dx

        def bwd_params_only(params, inputs, rng, dy):
            def f(p):
                return as_tuple(stack.apply(p, *inputs, dropout_rng=rng))

            _, vjp_fn = jax.vjp(f, params)
            (dparams,) = vjp_fn(dy)
            return dparams

        def grad_add(a, b):
            return jax.tree_util.tree_map(jnp.add, a, b)

        def update(params, opt_state, grads):
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_opt_state

        # raw closures retained for subclasses (the mesh engine fuses
        # accumulation AROUND these exact functions, so the two engines'
        # stage math has one definition and cannot drift)
        self._raw_fwd = fwd
        self._raw_bwd = bwd
        self._raw_bwd_params_only = bwd_params_only
        self.fwd = jax.jit(fwd)
        self.fwd_new_counters = fwd_new_counters
        self.fwd_counted = (
            jax.jit(fwd_counted,
                    donate_argnums=(3,) if _donation_enabled() else ())
            if self.has_counters else None
        )
        self.bwd = jax.jit(bwd)
        self.bwd_params_only = jax.jit(bwd_params_only)
        self.grad_add = jax.jit(grad_add)
        # donate the old params/opt_state: the caller rebinds both to the
        # update's outputs, so XLA can update buffers in place instead of
        # holding two copies of every stage's parameters during the step
        self.update = jax.jit(update, donate_argnums=(0, 1))
        # Donated twins for the pipeline issue loops only.  Donation
        # invariants: a stage's stored INPUT tuple is dead the moment its
        # backward issues (nothing reads it afterwards — remat re-derives
        # activations from it inside the same program), and a running grad
        # TOTAL is rebound to accumulate's output, so both buffers may be
        # reused in place.  The plain bwd/bwd_params_only/grad_add above
        # stay undonated because measure_stage_times re-executes them with
        # the SAME input buffers (a donated input is invalid on reuse).
        # The cotangent argument is never donated: the zero tail of dy is
        # a per-structure cached buffer shared across microbatches.
        if _donation_enabled():
            self.bwd_donated = jax.jit(bwd, donate_argnums=(1,))
            self.bwd_params_only_donated = jax.jit(
                bwd_params_only, donate_argnums=(1,)
            )
            self.grad_add_donated = jax.jit(grad_add, donate_argnums=(0,))
        else:
            self.bwd_donated = self.bwd
            self.bwd_params_only_donated = self.bwd_params_only
            self.grad_add_donated = self.grad_add


def cached_programs(key, factory):
    """Bounded-LRU lookup in the process-global program cache: one
    eviction/hit-count discipline shared by every program family (MPMD
    stage programs here, the mesh twins in mesh_pipeline.py)."""
    if key in _PROGRAM_CACHE:
        _PROGRAM_CACHE_STATS["hits"] += 1
        _PROGRAM_CACHE[key] = _PROGRAM_CACHE.pop(key)  # refresh LRU order
    else:
        _PROGRAM_CACHE_STATS["misses"] += 1
        while len(_PROGRAM_CACHE) >= PROGRAM_CACHE_MAX_ENTRIES:
            _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
        _PROGRAM_CACHE[key] = factory()
    return _PROGRAM_CACHE[key]


def get_stage_programs(layer_cfgs, optimizer) -> _StagePrograms:
    import json

    key = (
        json.dumps(list(layer_cfgs), sort_keys=True, default=str),
        id(optimizer),
        # donation is decided per-process but tests force it per-model;
        # keying on it keeps a forced build from serving cached undonated
        # programs (or vice versa)
        _donation_enabled(),
    )
    return cached_programs(
        key, lambda: _StagePrograms(layer_cfgs, optimizer)
    )


class StageRuntime:
    """One pipeline stage: layer slice + device + compiled programs."""

    #: injectable slowdown-emulation hooks: the emulation measures the
    #: program's blocked time with ``_clock`` and requests ``elapsed x
    #: (slowdown - 1)`` from ``_sleep``.  Tests substitute deterministic
    #: fakes so the emulated inflation is asserted exactly under any
    #: host load (the wall-clock A/B form of the assertion flaked in
    #: loaded full-suite runs).
    _clock = staticmethod(time.perf_counter)
    _sleep = staticmethod(time.sleep)

    def __init__(
        self,
        stage_index: int,
        layer_cfgs: Sequence[Dict],
        params: Sequence[Any],
        device,
        optimizer: optax.GradientTransformation,
        slowdown: float = 1.0,
        differentiable_inputs: bool = True,
    ):
        self.stage_index = stage_index
        self.device = device
        self.num_layers = len(layer_cfgs)
        # trace-lane name: one Perfetto process row per (stage, device);
        # tools/trace_report.py keys stage utilization on the "stage N"
        # prefix, so keep it first
        self.lane_name = f"stage {stage_index} [{device}]"
        self.slowdown = float(slowdown)
        self._differentiable_inputs = differentiable_inputs
        # canonical structure key: stages sharing it run the same compiled
        # programs, so their compute profile on a given device is identical
        import json as _json

        self.config_key = _json.dumps(list(layer_cfgs), sort_keys=True,
                                      default=str)

        programs = get_stage_programs(layer_cfgs, optimizer)
        self.stack = programs.stack
        self._fwd = programs.fwd
        self._fwd_counted = programs.fwd_counted
        self._fwd_new_counters = programs.fwd_new_counters
        # running totals of what the stage's layers sow, on the device
        # (None: the stage sows nothing, or has not run yet)
        self.counters = None
        # shapes of the first forward's and the first backward's arguments
        # (for ``scoped_instructions``; arrays are not kept)
        self._fwd_avals = None
        self._bwd_avals = None
        self._bwd = programs.bwd
        self._bwd_params_only = programs.bwd_params_only
        self._bwd_donated = programs.bwd_donated
        self._bwd_params_only_donated = programs.bwd_params_only_donated
        self._grad_add = programs.grad_add
        self._grad_add_donated = programs.grad_add_donated
        self._update = programs.update
        self._optimizer = optimizer

        self.params: List[Any] = jax.device_put(list(params), device)
        self.opt_state = jax.device_put(optimizer.init(self.params), device)

    # --- execution ----------------------------------------------------------
    def _emulate_slowdown(self, ref) -> None:
        """Heterogeneity emulation: block on ``ref`` and sleep
        ``elapsed x (slowdown - 1)``, through the injectable hooks."""
        if self.slowdown > 1.0:
            start = self._clock()
            jax.block_until_ready(ref)
            elapsed = self._clock() - start
            self._sleep(elapsed * (self.slowdown - 1.0))

    def forward(self, inputs: Tuple, rng) -> Tuple:
        inputs = device_put_elided(inputs, self.device)
        return self.forward_placed(inputs, rng)

    def forward_placed(self, inputs: Tuple, rng) -> Tuple:
        """Forward for inputs the caller already committed to this stage's
        device — the issue loops place inputs themselves (they also store
        them for backward), so the placement pass here would be a no-op
        tree traversal per microbatch per stage."""
        _DISPATCH_STATS["programs"] += 1
        if self._fwd_avals is None:
            self._fwd_avals = _avals((inputs, rng))
        if self._fwd_counted is None:
            out = self._fwd(self.params, inputs, rng)
        else:
            if self.counters is None:
                _, shapes = jax.eval_shape(
                    self._fwd_new_counters, self.params, inputs, rng)
                self.counters = jax.device_put(
                    jax.tree_util.tree_map(
                        lambda a: np.zeros(a.shape, a.dtype), shapes),
                    self.device,
                )
            out, self.counters = self._fwd_counted(
                self.params, inputs, rng, self.counters)
        self._emulate_slowdown(out)
        return out

    def forward_saving(self, inputs: Tuple, rng) -> Tuple[Any, Tuple]:
        """``(saved, outputs)``: the forward the issue loops drive.  They
        hold ``saved`` until the microbatch's backward is issued and hand
        it to ``backward_accumulate`` in ``inputs``' place: here it IS the
        placed inputs (the backward recomputes the stage from them)."""
        return inputs, self.forward_placed(inputs, rng)

    def timed_forward(self, inputs: Tuple, rng) -> Tuple:
        """The forward for a profiler that runs it again and again on the
        same buffers: nothing donated, nothing counted, nothing saved."""
        return self._fwd(self.params, inputs, rng)

    def timed_backward(self, inputs: Tuple, rng, dy: Tuple):
        """The backward for the same profiler, undonated: ``(gradients,
        dx)``, or the gradients alone where the inputs take none."""
        if self._differentiable_inputs:
            return self._bwd(self.params, inputs, rng, dy)
        return self._bwd_params_only(self.params, inputs, rng, dy)

    def timed_step(self, inputs: Tuple, rng, dy: Tuple):
        """One forward and one backward as a step issues them, for that
        profiler; returns something to block on."""
        self.timed_forward(inputs, rng)
        return self.timed_backward(inputs, rng, dy)

    def layer_counters(self) -> List[Any]:
        """What each of the stage's layers has sown, a layer an entry in
        layer order (``{}``: the layer sows nothing, or has not run)."""
        if self.counters is None:
            return [{} for _ in range(self.num_layers)]
        return list(self.counters)

    def backward(self, inputs: Tuple, rng, dy: Tuple):
        """Issue the donating backward: ``inputs`` is consumed (the issue
        loops own the last reference once a microbatch's backward goes
        out); profiling paths that re-execute with the same buffers must
        use ``timed_forward`` / ``timed_backward``."""
        dy = device_put_elided(dy, self.device)
        _DISPATCH_STATS["programs"] += 1
        if self._bwd_avals is None:
            self._bwd_avals = _avals((inputs, rng, dy))
        if self._differentiable_inputs:
            grads, dx = self._bwd_donated(self.params, inputs, rng, dy)
        else:
            grads = self._bwd_params_only_donated(
                self.params, inputs, rng, dy
            )
            dx = None
        self._emulate_slowdown(grads)
        return grads, dx

    def accumulate(self, total, grads):
        if total is None:
            return grads
        # the old total dies here (the caller rebinds to the sum), so the
        # donating twin lets XLA accumulate into its buffer in place
        _DISPATCH_STATS["programs"] += 1
        return self._grad_add_donated(total, grads)

    def backward_accumulate(self, total, inputs: Tuple, rng, dy: Tuple):
        """The fused issue point the schedules drive: one microbatch's
        backward plus accumulation into the running per-stage total,
        returning ``(new_total, dx)``.  The MPMD runtime issues two
        programs (bwd, then grad_add); the mesh-native runtime overrides
        this with ONE fused program — the gpipe/1f1b issue loops neither
        know nor care which engine they are driving."""
        grads, dx = self.backward(inputs, rng, dy)
        return self.accumulate(total, grads), dx

    def apply_gradients(self, grads) -> None:
        _DISPATCH_STATS["programs"] += 1
        self.params, self.opt_state = self._update(
            self.params, self.opt_state, grads
        )

    def program_holders(self) -> List["StageRuntime"]:
        """The runtimes whose programs this stage runs (itself)."""
        return [self]

    def compiled_programs(self) -> List[Any]:
        """The stage's forward and backward programs as compiled for the
        shapes they first ran with (``jax.stages.Compiled``: from the
        persistent cache where there is one, else a compile; never inside
        a timed window).  Empty until the stage has run."""
        if self._fwd_avals is None or self._bwd_avals is None:
            return []
        params = _avals(self.params)
        inputs, rng = self._fwd_avals
        if self._fwd_counted is None:
            fwd = self._fwd.lower(params, inputs, rng)
        else:
            fwd = self._fwd_counted.lower(params, inputs, rng,
                                          _avals(self.counters))
        bwd = (self._bwd_donated if self._differentiable_inputs
               else self._bwd_params_only_donated)
        return [fwd.compile(), bwd.lower(params, *self._bwd_avals).compile()]

    # --- weights exchange ---------------------------------------------------
    def get_state_dict(self) -> List[Any]:
        return jax.tree_util.tree_map(np.asarray, self.params)

    def load_weights(self, state_dict_list: Sequence[Any]) -> None:
        if len(state_dict_list) != self.num_layers:
            raise ValueError(
                f"stage {self.stage_index} holds {self.num_layers} layers, "
                f"got {len(state_dict_list)} state dicts"
            )
        self.params = jax.device_put(list(state_dict_list), self.device)
        self.opt_state = jax.device_put(
            self._optimizer.init(self.params), self.device
        )


class LayeredStageRuntime:
    """A pipeline stage that runs its layers as ONE PROGRAM A LAYER.

    ``StageRuntime`` compiles a stage's whole slice into one forward and
    one backward program: right for a stack of small equal layers (BERT:
    68 programs a step are already host-bound), wasteful for a few large
    layers of a few kinds, where every stage shape is a new executable
    as large as its layers together and a layer kind is compiled once a
    stage it appears in.  Here a stage holds one single-layer
    ``StageRuntime`` a layer; layers of one kind and config share their
    programs through the program cache whichever stage they sit in, so a
    model compiles one forward, one backward and one update a layer KIND,
    whatever the partition, and a re-allocation compiles nothing.  The
    allocator still decides which layers a worker (a device) holds.

    The cost is dispatches: one a layer instead of one a stage, and each
    layer keeps its input for its own backward (which recomputes the
    layer, not the stage).  ``PipelineModel`` takes this form where the
    model's layers are large (``programs_a_layer``).
    """

    def __init__(self, stage_index, layer_cfgs, params, device, optimizer,
                 slowdown: float = 1.0, differentiable_inputs: bool = True):
        import json as _json

        self.stage_index = stage_index
        self.device = device
        self.num_layers = len(layer_cfgs)
        self.lane_name = f"stage {stage_index} [{device}]"
        self.slowdown = float(slowdown)
        self._differentiable_inputs = differentiable_inputs
        self.config_key = _json.dumps(list(layer_cfgs), sort_keys=True,
                                      default=str)
        self.layers: List[StageRuntime] = [
            StageRuntime(
                stage_index, [cfg], [layer_params], device, optimizer,
                slowdown=slowdown,
                differentiable_inputs=differentiable_inputs or i > 0,
            )
            for i, (cfg, layer_params) in enumerate(zip(layer_cfgs, params))
        ]

    # what callers read off a stage
    @property
    def stack(self):
        from ..builder import LayerStack

        return LayerStack([l.stack.modules[0] for l in self.layers])

    @property
    def params(self) -> List[Any]:
        return [l.params[0] for l in self.layers]

    @params.setter
    def params(self, params) -> None:
        for layer, layer_params in zip(self.layers, params):
            layer.params = [layer_params]

    @property
    def opt_state(self) -> List[Any]:
        return [l.opt_state for l in self.layers]

    @opt_state.setter
    def opt_state(self, states) -> None:
        for layer, state in zip(self.layers, states):
            layer.opt_state = state

    def layer_counters(self) -> List[Any]:
        return [l.layer_counters()[0] for l in self.layers]

    def program_holders(self) -> List[StageRuntime]:
        return list(self.layers)

    # execution
    def _through(self, inputs: Tuple, rng, run) -> Tuple[List[Tuple], Tuple]:
        """``(each layer's input, the last layer's output)`` of ``run(layer,
        input, rng)`` through the layers in turn."""
        acts, layer_inputs = inputs, []
        for i, layer in enumerate(self.layers):
            layer_inputs.append(acts)
            acts = run(layer, acts, self._layer_rng(rng, i))
        return layer_inputs, acts

    def forward(self, inputs: Tuple, rng) -> Tuple:
        return self.forward_placed(
            device_put_elided(inputs, self.device), rng)

    def forward_placed(self, inputs: Tuple, rng) -> Tuple:
        """Forward alone (evaluation): no layer's input outlives the next
        layer's program."""
        return self._through(inputs, rng, StageRuntime.forward_placed)[1]

    def forward_saving(self, inputs: Tuple, rng) -> Tuple[Any, Tuple]:
        """``(saved, outputs)``: ``saved`` is every layer's input, which
        the issue loops hold until the backward (each layer's backward
        recomputes that layer from its own input)."""
        return self._through(inputs, rng, StageRuntime.forward_placed)

    def timed_forward(self, inputs: Tuple, rng) -> Tuple:
        return self._through(inputs, rng, StageRuntime.timed_forward)[1]

    def timed_step(self, inputs: Tuple, rng, dy: Tuple):
        layer_inputs, _ = self._through(inputs, rng,
                                        StageRuntime.timed_forward)
        grads: List[Any] = [None] * len(self.layers)
        for i in reversed(range(len(self.layers))):
            got = self.layers[i].timed_backward(
                layer_inputs[i], self._layer_rng(rng, i), dy)
            grads[i], dy = (
                got if self.layers[i]._differentiable_inputs else (got, None))
        return grads

    @staticmethod
    def _layer_rng(rng, i: int):
        if rng is None or i == 0:
            return rng
        _DISPATCH_STATS["programs"] += 1
        return _fold1(rng, i)

    def backward(self, saved: List[Tuple], rng, dy: Tuple):
        """``saved``: what ``forward_saving`` returned for the microbatch
        (consumed: each layer's backward donates its input)."""
        grads: List[Any] = [None] * len(self.layers)
        for i in reversed(range(len(self.layers))):
            (grads[i],), dy = self.layers[i].backward(
                saved[i], self._layer_rng(rng, i), dy)
        return grads, dy

    def accumulate(self, total, grads):
        if total is None:
            return grads
        return [layer.accumulate([t], [g])[0]
                for layer, t, g in zip(self.layers, total, grads)]

    def backward_accumulate(self, total, saved, rng, dy: Tuple):
        grads, dx = self.backward(saved, rng, dy)
        return self.accumulate(total, grads), dx

    def apply_gradients(self, grads) -> None:
        for layer, g in zip(self.layers, grads):
            layer.apply_gradients([g])

    def get_state_dict(self) -> List[Any]:
        return [l.get_state_dict()[0] for l in self.layers]

    def load_weights(self, state_dict_list: Sequence[Any]) -> None:
        if len(state_dict_list) != self.num_layers:
            raise ValueError(
                f"stage {self.stage_index} holds {self.num_layers} layers, "
                f"got {len(state_dict_list)} state dicts"
            )
        for layer, state in zip(self.layers, state_dict_list):
            layer.load_weights([state])


#: mean parameter bytes a layer from which a model's stages run one
#: program a LAYER (``LayeredStageRuntime``) instead of one a stage.  A
#: layer that reads tens of megabytes of weights runs for milliseconds on
#: any batch worth a chip, so a dispatch a layer hides behind the device,
#: and sharing executables by layer kind is what pays (compile time, cache
#: bytes).  Under it (BERT-large's 75 units average 18 MB) the host's issue
#: loop is the floor and a stage stays one program.  Between the two forms
#: in the benchmark (18 MB and 242 MB a layer) the line is not measured.
LAYER_PROGRAM_MIN_BYTES = 64 << 20


def programs_a_layer(params_by_layer: Sequence[Any]) -> bool:
    """Does a model with these parameter trees, a layer each, run one
    program a layer?  By what the engine can see: their mean size."""
    if not params_by_layer:
        return False
    total = sum(np.asarray(leaf).nbytes
                for leaf in jax.tree_util.tree_leaves(list(params_by_layer)))
    return total / len(params_by_layer) >= LAYER_PROGRAM_MIN_BYTES


@dataclass
class PipelineStats:
    """Wall-clock phase accounting for the last step.

    Under the 1F1B schedule forward and backward interleave, so their split
    is not observable: ``forward_s`` then holds the fused fwd+bwd time,
    ``backward_s`` is 0, and ``interleaved`` is True so consumers (logs,
    MetricsHook) can tell fused from free.
    """

    forward_s: float = 0.0
    backward_s: float = 0.0
    step_s: float = 0.0
    loss: float = 0.0
    interleaved: bool = False
    # host-overhead split (the dispatch-profiling record): dispatch_s is
    # the wall time the host spent ISSUING work (the fwd/bwd/update loops
    # before their blocking barriers) — the Python-loop tax the devices
    # cannot overlap away; compute_wait_s is the time spent blocked on
    # device completion.  transfers/transfers_elided count device_put
    # leaves moved vs skipped this step; compiles counts XLA backend
    # compiles triggered this step (0 in steady state).
    dispatch_s: float = 0.0
    compute_wait_s: float = 0.0
    transfers: int = 0
    transfers_elided: int = 0
    compiles: int = 0
    # host dispatches this step (see _DISPATCH_STATS): jitted-program
    # invocations and moving device_put calls — the count the mesh-native
    # engine collapses from O(devices) to O(stages) per microbatch tick
    program_dispatches: int = 0
    put_dispatches: int = 0
    # expert-layer counters, totals since the stages were built.  NOT
    # per-step: the stages accumulate them on the device and
    # ``PipelineModel.read_counters()`` (one device_get, called by whoever
    # wants them, outside any timed window) writes them here.
    tokens_routed_here: int = 0
    dropped_tokens: int = 0
    expert_load_max_over_mean: float = 0.0
    # calls of the dropless expert layer and the blocks of the expert order
    # they walked (``ops/moe_dropless.py``: 1 a call when the pairs routed
    # here fit one block)
    moe_blocks: int = 0
    moe_calls: int = 0

    #: metric classification (telemetry.MetricsRegistry contract): the
    #: model rebinds ``stats`` to a FRESH object every step, so every
    #: field here is a per-step gauge — none accumulates across steps
    FIELD_TYPES = {
        "forward_s": "gauge", "backward_s": "gauge", "step_s": "gauge",
        "loss": "gauge", "interleaved": "gauge", "dispatch_s": "gauge",
        "compute_wait_s": "gauge", "transfers": "gauge",
        "transfers_elided": "gauge", "compiles": "gauge",
        "program_dispatches": "gauge", "put_dispatches": "gauge",
        "tokens_routed_here": "gauge", "dropped_tokens": "gauge",
        "expert_load_max_over_mean": "gauge",
        "moe_blocks": "gauge", "moe_calls": "gauge",
    }

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able field dict — the ``ServingStats.snapshot()`` twin.

        Consumers (``MetricsHook``, ``MetricsRegistry``) iterate this
        instead of hand-copying field names, so a field added here
        reaches every metrics surface without further wiring.
        """
        import dataclasses

        return dataclasses.asdict(self)


class PipelineModel:
    """The assembled pipeline: stage runtimes in worker-rank order.

    Reference analog: ``RpcModel`` building one module per worker in pool
    order (``rpc_model.py:23-42``), except parameters come from the
    layer-indexed :class:`ParameterServer` (single source of truth), so a
    freshly-built pipeline always agrees with the host copy and checkpoints
    survive re-allocation.
    """

    def __init__(
        self,
        worker_manager: WorkerManager,
        parameter_server: ParameterServer,
        optimizer: optax.GradientTransformation,
        loss_fn: Callable[[jax.Array, jax.Array], jax.Array],
        devices: Optional[Sequence[Any]] = None,
        num_microbatches: int = 1,
        schedule: str = "gpipe",
    ):
        if schedule not in ("gpipe", "1f1b"):
            raise ValueError(f"unknown schedule {schedule!r}")
        self._worker_manager = worker_manager
        self._parameter_server = parameter_server
        self._optimizer = optimizer
        self._loss_fn = loss_fn
        self._devices = list(devices) if devices is not None else jax.devices()
        self.num_microbatches = num_microbatches
        self.schedule = schedule
        self.stats = PipelineStats()
        self._train = True
        self._fwd_call_count = 0
        self._grad_call_count = 0

        self.stages: List[StageRuntime] = []
        # zero-cotangent tails keyed by last-stage output structure: built
        # once, shared read-only across microbatches and steps (they are
        # never donated), instead of M fresh jnp.zeros_like tuples per step
        self._zero_tail_cache: Dict = {}
        # dispatch accounting for the most recent compute_gradients call
        self._last_dispatch_s = 0.0
        _ensure_compile_listener()
        self._build_stages()
        self._last_device = self.stages[-1].device
        self._compile_loss()

    def _compile_loss(self) -> None:
        loss_fn = self._loss_fn  # bind by value: jit traces this closure

        def loss_and_dlogits(logits, labels, scale):
            def f(lg):
                return loss_fn(lg, labels) * scale

            loss, dlogits = jax.value_and_grad(f)(logits)
            return loss, dlogits

        self._loss_and_dlogits = jax.jit(loss_and_dlogits)

    def set_loss_fn(self, loss_fn: Callable) -> None:
        """Swap the loss; recompiles so cached traces can't keep the old one."""
        self._loss_fn = loss_fn
        self._compile_loss()

    # --- construction -------------------------------------------------------
    def _build_stages(self) -> None:
        self.stages = []
        layer_cursor = 0
        workers = sorted(
            self._worker_manager.worker_pool, key=lambda w: w.rank
        )
        stage_idx = 0
        stage_class = (LayeredStageRuntime
                       if programs_a_layer(self._parameter_server.params)
                       else StageRuntime)
        for worker in workers:
            layer_cfgs = worker.model_config or []
            if not layer_cfgs:
                continue
            params = self._parameter_server.get_layer_slice(
                layer_cursor, layer_cursor + len(layer_cfgs)
            )
            device = self._devices[worker.device_index % len(self._devices)]
            self.stages.append(
                stage_class(
                    stage_index=stage_idx,
                    layer_cfgs=layer_cfgs,
                    params=params,
                    device=device,
                    optimizer=self._optimizer,
                    slowdown=float(worker.extra_config.get("slowdown", 1.0)),
                    differentiable_inputs=stage_idx > 0,
                )
            )
            layer_cursor += len(layer_cfgs)
            stage_idx += 1
        if layer_cursor != self._parameter_server.num_layers:
            raise ValueError(
                f"workers cover {layer_cursor} layers but the model has "
                f"{self._parameter_server.num_layers} — run an allocator first"
            )

    def rebuild(self) -> None:
        """Re-slice stages after a re-allocation (gathers weights first)."""
        self.sync_to_parameter_server()
        self._build_stages()
        self._last_device = self.stages[-1].device
        self._zero_tail_cache.clear()  # the last stage may have moved

    def _zero_tail(self, acts: Tuple) -> Tuple:
        """Zero cotangents for ``acts[1:]`` on the last stage's device.

        Non-loss outputs of the final stage (attention masks, pass-through
        activations) get zero cotangents; the buffers are structure-keyed
        and reused across microbatches and steps — backward never donates
        its cotangent argument, so sharing is safe.
        """
        if not HOTPATH:
            return tuple(jnp.zeros_like(x) for x in acts[1:])
        key = tuple((tuple(x.shape), str(x.dtype)) for x in acts[1:])
        cached = self._zero_tail_cache.get(key)
        if cached is None:
            cached = tuple(
                jax.device_put(jnp.zeros(x.shape, x.dtype),
                               self._last_device)
                for x in acts[1:]
            )
            self._zero_tail_cache[key] = cached
        return cached

    # --- reference-API surface ---------------------------------------------
    @property
    def model(self) -> List[StageRuntime]:
        """Stage list (reference: ``RpcModel.model``)."""
        return self.stages

    def train(self, mode: bool = True) -> None:
        """Train/eval switch: in eval mode ``forward`` runs without dropout
        rngs (layers with live dropout still need ``deterministic`` configs
        for bit-identical eval; ``train_step`` always trains)."""
        self._train = mode

    # --- execution ----------------------------------------------------------
    def forward(self, data, rng: Optional[jax.Array] = None):
        """Inference/eval forward of one full batch (no microbatching).

        In train mode with no explicit ``rng``, each call folds a
        monotonically increasing counter into a fixed base key, so repeated
        calls draw fresh dropout masks (a bare ``key(0)`` default would
        silently reuse the same mask every call).
        """
        if rng is None and self._train:
            rng = jax.random.fold_in(jax.random.key(0), self._fwd_call_count)
            self._fwd_call_count += 1
        acts = as_tuple(data)
        fold = _fold1 if HOTPATH else jax.random.fold_in
        for k, stage in enumerate(self.stages):
            stage_rng = fold(rng, k) if rng is not None else None
            acts = stage.forward(acts, stage_rng)
        return acts[0]

    def train_step(
        self,
        data,
        labels,
        rng: Optional[jax.Array] = None,
    ) -> float:
        """One optimizer step: microbatched fwd -> loss -> bwd -> update.

        Returns the mean loss over the batch.  Dispatch is asynchronous: with
        M microbatches the stages overlap GPipe-style without any explicit
        schedule — each device's work queue serializes its own stage while
        transfers ride ICI in parallel.  With ``schedule="1f1b"`` each
        microbatch's backward is issued as soon as its forward clears the
        last stage, capping per-stage live inputs at the pipeline depth
        instead of M.
        """
        sp = span_sinks()
        host = sp.lane(*_HOST_LANE)
        with sp.span("sky.pipe.step", host):
            compiles0 = xla_compile_count()
            copies0 = _TRANSFER_STATS["copies"]
            elided0 = _TRANSFER_STATS["elided"]
            programs0 = _DISPATCH_STATS["programs"]
            puts0 = _DISPATCH_STATS["puts"]
            grad_totals, losses, (t0, t1, t2) = self.compute_gradients(
                data, labels, rng
            )
            self.apply_gradients(grad_totals)
            t_upd_issued = time.perf_counter()
            with sp.span("sky.pipe.wait", host, {"what": "update"}):
                jax.block_until_ready(self.stages[0].params)
            t3 = time.perf_counter()

            dispatch_s = self._last_dispatch_s + (t_upd_issued - t2)
            with sp.span("sky.pipe.loss_get", host):
                total_loss = float(sum(jax.device_get(l) for l in losses))
            self.stats = PipelineStats(
                forward_s=t1 - t0, backward_s=t2 - t1, step_s=t3 - t2,
                loss=total_loss, interleaved=self._interleaved,
                dispatch_s=dispatch_s,
                compute_wait_s=max((t3 - t0) - dispatch_s, 0.0),
                transfers=_TRANSFER_STATS["copies"] - copies0,
                transfers_elided=_TRANSFER_STATS["elided"] - elided0,
                compiles=xla_compile_count() - compiles0,
                program_dispatches=_DISPATCH_STATS["programs"] - programs0,
                put_dispatches=_DISPATCH_STATS["puts"] - puts0,
            )
        return total_loss

    def _layer_counters(self) -> List[Any]:
        return [c for stage in self.stages for c in stage.layer_counters()]

    def read_counters(self) -> Dict[str, Any]:
        """The running totals the stages' layers have sown since they were
        built, read from the devices in ONE ``device_get`` (a host sync:
        call it outside a timed window).  Returns ``{"expert_tokens": [one
        int array a counting layer, in layer order], "tokens_routed_here",
        "dropped_tokens", "expert_load_max_over_mean", "moe_blocks",
        "moe_calls"}`` and writes the five scalars into ``self.stats``;
        ``{}`` if no layer counts."""
        sown: Dict[str, List[Any]] = {"moe": [], "moe_blocks": []}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self._layer_counters())[0]:
            name = str(getattr(path[-1], "key", ""))
            if name in sown and not _is_last(path):
                sown[name].append(leaf)
        if not sown["moe"]:
            return {}
        sown = {name: [np.asarray(v) for v in values]
                for name, values in jax.device_get(sown).items()}
        # a layer's vector: tokens to each held expert ..., routed, dropped
        tokens = [v[:-2] for v in sown["moe"]]
        routed = int(sum(int(v[-2]) for v in sown["moe"]))
        dropped = int(sum(int(v[-1]) for v in sown["moe"]))
        ratios = [float(t.max() / t.mean()) for t in tokens if t.sum() > 0]
        skew = max(ratios) if ratios else 0.0
        # a layer's pair: blocks walked, calls
        blocks, calls = (int(sum(int(v[i]) for v in sown["moe_blocks"]))
                         for i in (0, 1))
        self.stats.tokens_routed_here = routed
        self.stats.dropped_tokens = dropped
        self.stats.expert_load_max_over_mean = skew
        self.stats.moe_blocks = blocks
        self.stats.moe_calls = calls
        return dict(expert_tokens=tokens, tokens_routed_here=routed,
                    dropped_tokens=dropped, expert_load_max_over_mean=skew,
                    moe_blocks=blocks, moe_calls=calls)

    def last_sown(self) -> List[Dict[str, Any]]:
        """What each layer's LAST forward sowed under a ``last_`` name (the
        router's input and choices, the scan's inputs and final state), a
        layer an entry in layer order, ``{}`` where a layer sows none: the
        device arrays themselves, nothing fetched.  They are the timed
        programs' own values: whoever holds them to a reference reads
        them outside a timed window."""
        def last_of(tree):
            if not isinstance(tree, dict):
                return {}
            found = {}
            for name, value in tree.items():
                if str(name).startswith("last_"):
                    found[name] = value
                else:
                    found.update(last_of(value))
            return found

        return [last_of(c) for c in self._layer_counters()]

    def scoped_instructions(self, scopes: Sequence[str]):
        """``[(program, instruction, result type, scope)]`` over the stage
        programs that have run: which HLO instruction of which program
        (``jit_fwd_counted``, ``jit_bwd`` ...) lies under which named
        scope (``""``: under none of them; kept, because two programs of
        one name can hold an instruction of one name).  Stages that share
        programs are read once.  Costs a cache
        load (or a compile) a program: call it outside a timed window."""
        rows, seen = set(), set()
        holders = [h for stage in self.stages
                   for h in stage.program_holders()]
        for holder in holders:
            key = (holder.config_key, holder._differentiable_inputs)
            if key in seen:
                continue
            seen.add(key)
            for compiled in holder.compiled_programs():
                text = compiled.as_text()
                program = text.split(None, 2)[1].rstrip(",")
                rows.update((program, *row) for row in
                            scoped_instructions_of(text, scopes))
        return sorted(rows)

    def _span_lanes(self):
        """(span sinks, the host lane, per-stage lane list).

        Hoisted out of the issue loops: one ``span_sinks()`` and S + 1
        lane lookups per compute_gradients / apply_gradients call, zero
        per microbatch; with the ring off the lanes are all ``None``.
        """
        sp = span_sinks()
        return sp, sp.lane(*_HOST_LANE), [
            sp.lane(stage.lane_name, "dispatch") for stage in self.stages
        ]

    @property
    def _interleaved(self) -> bool:
        """True when gradients come from the fused-fwd/bwd 1F1B path (the
        single source for both schedule dispatch and stats labeling)."""
        return self.schedule == "1f1b" and self.num_microbatches > 1

    def _step_rngs(self, rng, M: int, S: int):
        """The per-(microbatch, stage) rng table the issue loops index.

        Engine hook: the MPMD runtime pre-folds keys host-side (one
        jitted pair-fold per cell); the mesh-native runtime overrides
        this with zero-dispatch ``(base, m, k)`` triples folded INSIDE
        each stage program (identical threefry math either way).
        """
        return _step_rngs(rng, M, S)

    def _loss_dispatch(self, logits, labels, scale):
        """One counted invocation of the compiled loss+dlogits program."""
        _DISPATCH_STATS["programs"] += 1
        return self._loss_and_dlogits(logits, labels, scale)

    def compute_gradients(
        self,
        data,
        labels,
        rng: Optional[jax.Array] = None,
        block: bool = True,
    ):
        """Schedule-dispatched fwd/bwd without the update: (per-stage grad
        totals, per-microbatch scaled losses, phase timestamps).

        The split from ``apply_gradients`` is what data-parallel replication
        builds on: replicas compute grads independently, average, then each
        applies the same averaged update — under EITHER schedule (1F1B's
        depth-bounded activation memory survives DP replication because
        the dispatch happens here, not in ``train_step``).  ``block=False``
        skips the ``block_until_ready`` barriers so a caller can dispatch
        several replicas' work before any of it completes (the timestamps
        then measure dispatch, not compute).  Under 1F1B forward/backward
        interleave, so the middle timestamp equals the last one and the
        fused time reads as "forward".
        """
        if self._interleaved:
            return self._compute_gradients_1f1b(data, labels, rng, block)
        return self._compute_gradients_gpipe(data, labels, rng, block)

    def _compute_gradients_gpipe(
        self,
        data,
        labels,
        rng: Optional[jax.Array] = None,
        block: bool = True,
    ):
        if rng is None:
            # deterministic default: fold a per-call counter into a fixed
            # base key so identically-seeded runs replay identically (a
            # wall-clock seed would differ run to run)
            rng = jax.random.fold_in(jax.random.key(1), self._grad_call_count)
            self._grad_call_count += 1
        M = self.num_microbatches
        scale = 1.0 / M
        sp, host, lanes = self._span_lanes()

        # ---- prefetch: split, then issue every host->device input/label
        # transfer up front so the copies ride the async queues UNDER the
        # first microbatches' compute instead of serializing inside the loops
        with sp.span("sky.pipe.prefetch", host):
            micro_data = _split_microbatches(as_tuple(data), M)
            micro_labels = _split_microbatches(labels, M)
            t0 = time.perf_counter()
            if HOTPATH:
                first_device = self.stages[0].device
                micro_data = [
                    device_put_elided(md, first_device) for md in micro_data
                ]
                micro_labels = [
                    device_put_elided(ml, self._last_device)
                    for ml in micro_labels
                ]

        # ---- forward (fill): per microbatch, per stage; keep stage inputs
        stage_inputs: List[List[Tuple]] = [[] for _ in self.stages]
        final_acts_per_mb: List[Tuple] = []
        with sp.span("sky.pipe.rng", host):
            rngs = self._step_rngs(rng, M, len(self.stages))
        with sp.span("sky.pipe.fwd_issue", host):
            for m in range(M):
                acts = micro_data[m]
                for k, stage in enumerate(self.stages):
                    acts = device_put_elided(acts, stage.device)
                    with sp.span("sky.pipe.fwd", lanes[k],
                                 {"stage": k, "mb": m}, ring="fwd"):
                        saved, acts = stage.forward_saving(acts, rngs[m][k])
                    stage_inputs[k].append(saved)
                final_acts_per_mb.append(acts)
        dispatch_s = time.perf_counter() - t0
        if block:
            with sp.span("sky.pipe.wait", host, {"what": "fwd"}):
                jax.block_until_ready(final_acts_per_mb[-1])
        t1 = time.perf_counter()

        # ---- loss + backward (drain), accumulating grads per stage
        grad_totals: List[Any] = [None] * len(self.stages)
        losses = []
        with sp.span("sky.pipe.bwd_issue", host):
            for m in reversed(range(M)):
                final_acts = final_acts_per_mb[m]
                with sp.span("sky.pipe.loss", host, {"mb": m}):
                    labels_m = device_put_elided(
                        micro_labels[m], self._last_device
                    )
                    loss_m, dlogits = self._loss_dispatch(
                        final_acts[0], labels_m, scale
                    )
                    losses.append(loss_m)
                    dy: Optional[Tuple] = (
                        (dlogits,) + self._zero_tail(final_acts)
                    )
                for k in reversed(range(len(self.stages))):
                    stage = self.stages[k]
                    with sp.span("sky.pipe.bwd", lanes[k],
                                 {"stage": k, "mb": m}, ring="bwd"):
                        grad_totals[k], dx = stage.backward_accumulate(
                            grad_totals[k], stage_inputs[k][m],
                            rngs[m][k], dy
                        )
                    dy = dx
        dispatch_s += time.perf_counter() - t1
        self._last_dispatch_s = dispatch_s
        if block:
            with sp.span("sky.pipe.wait", host, {"what": "bwd"}):
                jax.block_until_ready(grad_totals[0])
        t2 = time.perf_counter()
        return grad_totals, losses, (t0, t1, t2)

    def apply_gradients(self, grad_totals) -> None:
        """Apply per-stage gradient totals with each stage's optimizer."""
        sp, host, lanes = self._span_lanes()
        with sp.span("sky.pipe.update_issue", host):
            for k, stage in enumerate(self.stages):
                with sp.span("sky.pipe.update", lanes[k], {"stage": k},
                             ring="update"):
                    stage.apply_gradients(grad_totals[k])

    def _compute_gradients_1f1b(self, data, labels, rng, block: bool = True):
        """One-forward-one-backward schedule: issue each microbatch's
        backward as soon as its forward drains the last stage.

        Host-side this is a dependency-driven issue loop over per-stage op
        queues (warmup fwds, then alternating B/F, then drain), the classic
        non-interleaved 1F1B.  A stage's stored input for microbatch m is
        freed when its backward is issued, so live activations per stage
        are bounded by the pipeline depth rather than M.
        """
        if rng is None:
            rng = jax.random.fold_in(jax.random.key(1), self._grad_call_count)
            self._grad_call_count += 1
        M = self.num_microbatches
        S = len(self.stages)
        scale = 1.0 / M
        sp, host, lanes = self._span_lanes()

        with sp.span("sky.pipe.prefetch", host):
            micro_data = _split_microbatches(as_tuple(data), M)
            micro_labels = _split_microbatches(labels, M)
        with sp.span("sky.pipe.rng", host):
            rngs = self._step_rngs(rng, M, S)

        t0 = time.perf_counter()
        # prefetch (see the GPipe path): inputs to stage 0, labels to the
        # last stage, all issued before the first forward
        if HOTPATH:
            with sp.span("sky.pipe.prefetch", host):
                first_device = self.stages[0].device
                micro_data = [
                    device_put_elided(md, first_device)
                    for md in micro_data
                ]
                micro_labels = [
                    device_put_elided(ml, self._last_device)
                    for ml in micro_labels
                ]
        # live state
        stage_inputs: List[Dict[int, Tuple]] = [dict() for _ in range(S)]
        stage_outputs: List[Dict[int, Tuple]] = [dict() for _ in range(S)]
        dys: List[Dict[int, Tuple]] = [dict() for _ in range(S)]
        grad_totals: List[Any] = [None] * S
        losses: List[Any] = []
        fwd_next = [0] * S  # next microbatch each stage will forward
        bwd_next = [0] * S  # next microbatch each stage will backward

        def can_fwd(k):
            m = fwd_next[k]
            if m >= M:
                return False
            return k == 0 or m in stage_outputs[k - 1]

        def can_bwd(k):
            m = bwd_next[k]
            if m >= M or m not in stage_inputs[k]:
                return False
            # cotangent source: own fwd's dlogits for the last stage,
            # the next stage's input-cotangent otherwise
            return m in (dys[k] if k == S - 1 else dys[k + 1])

        def do_fwd(k):
            m = fwd_next[k]
            stage = self.stages[k]
            acts = (
                micro_data[m] if k == 0 else stage_outputs[k - 1].pop(m)
            )
            with sp.span("sky.pipe.fwd_issue", host):
                acts = device_put_elided(acts, stage.device)
                with sp.span("sky.pipe.fwd", lanes[k],
                             {"stage": k, "mb": m}, ring="fwd"):
                    stage_inputs[k][m], out = stage.forward_saving(
                        acts, rngs[m][k])
            if k < S - 1:
                stage_outputs[k][m] = out
            else:
                # the loss opens the microbatch's backward: it is issued
                # here, the moment the last stage's forward is, and
                # belongs to the backward's issue time
                with sp.span("sky.pipe.bwd_issue", host), \
                        sp.span("sky.pipe.loss", host, {"mb": m}):
                    labels_m = device_put_elided(
                        micro_labels[m], self._last_device
                    )
                    loss_m, dlogits = self._loss_dispatch(
                        out[0], labels_m, scale
                    )
                    losses.append(loss_m)
                    dys[k][m] = (dlogits,) + self._zero_tail(out)
            fwd_next[k] += 1

        def do_bwd(k):
            m = bwd_next[k]
            stage = self.stages[k]
            dy = dys[k].pop(m) if k == S - 1 else dys[k + 1].pop(m)
            with sp.span("sky.pipe.bwd_issue", host), \
                    sp.span("sky.pipe.bwd", lanes[k],
                            {"stage": k, "mb": m}, ring="bwd"):
                grad_totals[k], dx = stage.backward_accumulate(
                    grad_totals[k], stage_inputs[k].pop(m), rngs[m][k], dy
                )
            if k > 0:
                dys[k][m] = dx
            bwd_next[k] += 1

        # issue loop: walk stages last-to-first preferring backwards (they
        # free memory), then first-to-last issuing forwards; every pass
        # makes progress until all backwards are issued
        while any(b < M for b in bwd_next):
            progressed = False
            for k in reversed(range(S)):
                if can_bwd(k):
                    # classic 1F1B warmup: stage k delays its first backward
                    # until S-1-k forwards are in flight or forwards are done
                    if (
                        fwd_next[k] - bwd_next[k] >= min(S - k, M - bwd_next[k])
                        or fwd_next[k] >= M
                    ):
                        do_bwd(k)
                        progressed = True
            for k in range(S):
                if can_fwd(k):
                    do_fwd(k)
                    progressed = True
            if not progressed:  # pragma: no cover - schedule deadlock guard
                raise RuntimeError("1F1B schedule made no progress")

        self._last_dispatch_s = time.perf_counter() - t0
        if block:
            with sp.span("sky.pipe.wait", host, {"what": "bwd"}):
                jax.block_until_ready(grad_totals[0])
        t2 = time.perf_counter()
        # fused fwd/bwd: report (t0, t2, t2) so forward_s carries the whole
        # interleaved time and backward_s reads 0, as the stats contract
        # for interleaved schedules expects
        return grad_totals, losses, (t0, t2, t2)

    # --- profiling ----------------------------------------------------------
    def measure_stage_times(
        self,
        data,
        rng: Optional[jax.Array] = None,
        repeats: int = 3,
        inner_iters=3,
        dedup: bool = True,
        auto_window_s: float = 0.5,
        seed_times: Optional[Dict] = None,
    ) -> List[float]:
        """Real per-stage forward+backward seconds on their devices.

        Warm-compiles first, then takes the median of ``repeats`` samples,
        each timing ``inner_iters`` chained fwd+bwd executions with ONE
        final block — chaining amortizes per-call dispatch latency (which
        can exceed small-stage compute) out of the per-iteration figure.
        This is the honest per-stage cost profile the pipelined step time
        is built from — per-call elapsed times inside a full step are
        polluted by queueing.

        ``inner_iters="auto"`` sizes the chain per stage from a single
        post-warm probe execution: ``clamp(round(auto_window_s / t1), 1,
        3)``.  Fixed chaining either wastes wall clock on big stages
        (inner=3 on a 2 s slice) or leaves small stages dispatch-biased
        (inner=1 on a 0.2 s slice counts ~1-2% dispatch overhead as
        compute) — and since an optimal allocation's stages are smaller
        than an even allocation's, that bias systematically *understates*
        the optimal-vs-even headline.

        ``dedup`` reuses the measurement of an earlier stage with the same
        (layer structure, input signature, physical device): deep pipelines
        repeat a handful of slice shapes, so this cuts the number of timed
        loops (and remote-device round trips) by ~an order of magnitude.
        The untimed chained forward still runs once per stage to produce
        the next stage's inputs.

        Each reported time is multiplied by the stage's ``slowdown``
        factor (the emulated-degradation knob ``StageRuntime`` applies in
        ``train_step``): the raw jitted programs timed here bypass the
        slowdown sleep, so without the multiplier a fault-injected or
        stimulator-emulated straggler would be invisible to exactly the
        measurement pass the self-healing re-allocation relies on.  The
        dedup cache stores RAW times, so stages sharing programs but
        emulating different node speeds stay distinct.

        ``seed_times``: optional cross-call (key -> seconds) map.  Keys
        present are trusted as prior measurements (only the untimed
        forward runs for those stages); new measurements are written
        back.  This is what makes an incremental re-measure after a
        small allocation change cost one or two stages instead of the
        whole pipeline — callers that mutate slices (e.g. the
        measured-time bottleneck polish in bench.py) pass the same dict
        across calls.
        """
        if rng is None:
            rng = jax.random.key(0)
        acts = as_tuple(data)
        times: List[float] = []
        seen: Dict = seed_times if seed_times is not None else {}
        for k, stage in enumerate(self.stages):
            stage_rng = jax.random.fold_in(rng, k)
            inputs = device_put_elided(acts, stage.device)
            out = stage.timed_forward(inputs, stage_rng)
            key = (
                stage.config_key,
                tuple((tuple(x.shape), str(x.dtype)) for x in inputs),
                stage.device,
            )
            if dedup and key in seen:
                times.append(seen[key] * max(stage.slowdown, 1.0))
                acts = jax.tree_util.tree_map(np.asarray, out)
                continue
            dy = jax.tree_util.tree_map(jnp.zeros_like, out)

            def one_iter():
                return stage.timed_step(inputs, stage_rng, dy)

            # warm both programs
            jax.block_until_ready(one_iter())

            if inner_iters == "auto":
                t0 = time.perf_counter()
                jax.block_until_ready(one_iter())
                t1 = time.perf_counter() - t0
                inner = max(1, min(3, round(auto_window_s / max(t1, 1e-9))))
            else:
                inner = int(inner_iters)

            samples = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                g = None
                for _ in range(inner):
                    g = one_iter()
                jax.block_until_ready(g)
                samples.append(
                    (time.perf_counter() - t0) / max(inner, 1)
                )
            t_stage = float(np.median(samples))
            seen[key] = t_stage
            times.append(t_stage * max(stage.slowdown, 1.0))
            acts = jax.tree_util.tree_map(np.asarray, out)
        return times

    # --- training state (optimizer) -----------------------------------------
    def partition_signature(self) -> List[int]:
        """Layer counts per stage — identifies the current allocation."""
        return [stage.num_layers for stage in self.stages]

    def get_optimizer_state(self) -> Dict:
        """Host copy of every stage's optimizer state, tagged with the
        partition it belongs to.

        Unlike parameters (layer-indexed, partition-independent), optimizer
        state pytrees are shaped per-stage, so restoring requires the SAME
        allocation; the signature makes a mismatch detectable instead of
        silently corrupting momentum.
        """
        from flax import serialization

        return {
            "partition": self.partition_signature(),
            "stages": [
                serialization.to_state_dict(
                    jax.tree_util.tree_map(np.array, stage.opt_state)
                )
                for stage in self.stages
            ],
        }

    def load_optimizer_state(self, state: Dict) -> None:
        from flax import serialization

        saved = list(state["partition"])
        if saved != self.partition_signature():
            raise ValueError(
                f"optimizer state was saved under partition {saved}, "
                f"current partition is {self.partition_signature()}; "
                "re-allocate to match or restore parameters only"
            )
        for stage, stage_state in zip(self.stages, state["stages"]):
            restored = serialization.from_state_dict(
                stage.opt_state, stage_state
            )
            stage.opt_state = jax.device_put(restored, stage.device)

    # --- weights ------------------------------------------------------------
    def sync_to_parameter_server(self) -> None:
        """Gather every stage's layer params back into the host copy."""
        cursor = 0
        for stage in self.stages:
            for layer_params in stage.get_state_dict():
                self._parameter_server.update_weights(layer_params, cursor)
                cursor += 1

    def load_from_parameter_server(self) -> None:
        cursor = 0
        for stage in self.stages:
            stage.load_weights(
                self._parameter_server.get_layer_slice(
                    cursor, cursor + stage.num_layers
                )
            )
            cursor += stage.num_layers


__all__ = [
    "PipelineModel",
    "StageRuntime",
    "PipelineStats",
    "device_put_elided",
    "hotpath_counters",
    "xla_compile_count",
    "clear_program_cache",
]
