"""skylint: an AST linter for the JAX hazards this repo actually hits.

Generic linters cannot see the failure modes that cost this codebase real
wall clock: a stray ``.item()`` inside the pipeline issue loop serializes
every device queue; a ``jax.jit`` created per step retraces forever; a
reused PRNG key silently correlates dropout masks; a read of a donated
buffer is poison on TPU and invisible on CPU.  Each rule below encodes one
of those hazards with a stable ID, a fix-it message, and inline
suppression:

    SKY001  host-device sync inside a hot path
    SKY002  recompile hazard (jit-per-call, traced branching, bad statics)
    SKY003  PRNG discipline (key reuse, dead split results, stale keys)
    SKY004  read of a buffer after donation (``donate_argnums``)
    SKY005  timing a dispatch region without ``block_until_ready``
    SKY006  debug leftovers (``jax.debug.print``, ``breakpoint()``, pdb)
    SKY007  layer-config structure (``layer_type`` missing from a unit)
    SKY008  tuple-threading protocol (raw ``.apply`` result star-unpacked
            without ``as_tuple``)

Suppression syntax (same line as the finding)::

    total = float(loss)  # skylint: disable=SKY001  -- once-per-step read

or ``# skylint: disable`` to silence every rule on that line; a line
containing ``# skylint: disable-file=SKY00X`` disables a rule for the
whole file.  Parse failures surface as rule ``SKY000`` so a broken file
cannot slip through a lint gate as "no findings".

The rules are heuristic by design — AST-level, no type inference — and
tuned to be quiet on this tree: the self-lint gate
(``python -m tools.skylint skycomputing_tpu/ --strict``) ships green.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

# --------------------------------------------------------------------------
# model
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Finding:
    """One lint finding, pinned to a file position."""

    rule: str
    path: str
    line: int
    col: int
    message: str
    fixit: str
    suppressed: bool = False

    def format(self) -> str:
        return (
            f"{self.path}:{self.line}:{self.col}: {self.rule} "
            f"{self.message}  [fix: {self.fixit}]"
        )

    def to_dict(self) -> Dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "col": self.col,
            "message": self.message,
            "fixit": self.fixit,
            "suppressed": self.suppressed,
        }


@dataclass
class LintConfig:
    """Rule selection + suppression handling for one lint run."""

    select: Optional[Set[str]] = None  # None = all rules
    ignore: Set[str] = field(default_factory=set)
    include_suppressed: bool = False  # report suppressed findings too


# functions whose bodies are "hot": they run once per training step (or
# more — per microbatch, per stage) and host-side stalls in them serialize
# the device queues.  Nested functions inherit hotness from the enclosing
# definition.
HOT_FN_RE = re.compile(
    r"^(train_step|forward|forward_placed|forward_saving|backward"
    r"|compute_gradients|_compute_gradients\w*|do_fwd|do_bwd|accumulate"
    r"|apply_gradients"
    r"|before_train_iter|after_train_iter|before_iter|after_iter"
    r"|_train_loop|issue\w*)$"
)

# calls that force a device->host sync (or a host round trip) when handed
# a jax.Array
_SYNC_CALL_NAMES = {"float", "int", "bool"}
_SYNC_ATTR_TAILS = {"item", "tolist"}
_SYNC_NP_FNS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jax.device_get",
}

# dispatch-looking callees for SKY005 (timing honesty): jitted handles,
# jax/jnp API calls, and the stage-program idioms of this repo
_DISPATCHY_TAIL_RE = re.compile(
    r"^_?(fwd|bwd|bwd_params_only|forward|backward|train_step|apply"
    r"|update|one_iter|step|init)(_donated)?$"
)
_SYNCING_TAILS = {"block_until_ready", "device_get", "item", "asarray",
                  "array", "tolist"}

# jax/jnp API that never dispatches device work: abstract evaluation,
# dtype/shape queries, pytree plumbing — timing across ONLY these is
# honest host timing, not an async-dispatch hazard
_NON_DISPATCH_JAX = {
    "jax.eval_shape", "jax.ShapeDtypeStruct", "jax.typeof",
    "jnp.issubdtype", "jnp.dtype", "jnp.shape", "jnp.result_type",
    "jnp.ndim", "jax.dtypes.canonicalize_dtype", "jax.dtypes.result_type",
}

_SUPPRESS_LINE_RE = re.compile(
    r"#\s*skylint:\s*disable(?:=([A-Za-z0-9_,\s]+))?"
)
_SUPPRESS_FILE_RE = re.compile(
    r"#\s*skylint:\s*disable-file=([A-Za-z0-9_,\s]+)"
)


# --------------------------------------------------------------------------
# AST helpers
# --------------------------------------------------------------------------


def _dotted(node: ast.AST) -> Optional[str]:
    """'jax.random.split' for an Attribute/Name chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_tail(call: ast.Call) -> str:
    """Last segment of the callee ('split' for jax.random.split(...))."""
    fn = call.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return ""


def _is_jax_jit_call(call: ast.Call) -> bool:
    name = _dotted(call.func)
    return name in ("jax.jit", "jit")


def _walk_functions(tree: ast.Module):
    """Yield (function_node, is_hot) for every def, hotness inherited."""

    def visit(node, hot):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                child_hot = hot or bool(HOT_FN_RE.match(child.name))
                yield child, child_hot
                yield from visit(child, child_hot)
            else:
                yield from visit(child, hot)

    yield from visit(tree, False)


def _own_nodes(fn: ast.AST) -> Iterable[ast.AST]:
    """Walk a function body WITHOUT descending into nested defs."""

    def visit(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                continue
            yield child
            yield from visit(child)

    yield from visit(fn)


def _assign_target_names(node: ast.AST) -> List[str]:
    """Plain-Name targets of an Assign/AugAssign/For/With target tree."""
    out: List[str] = []

    def collect(t):
        if isinstance(t, ast.Name):
            out.append(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                collect(e)
        elif isinstance(t, ast.Starred):
            collect(t.value)

    if isinstance(node, ast.Assign):
        for t in node.targets:
            collect(t)
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign, ast.For)):
        collect(node.target)
    return out


# --------------------------------------------------------------------------
# rules
# --------------------------------------------------------------------------


class _Ctx:
    def __init__(self, tree: ast.Module, path: str, lines: List[str]):
        self.tree = tree
        self.path = path
        self.lines = lines
        # names bound (anywhere in the module) to a jax.jit(...) result —
        # used by SKY002/SKY005 to recognize jitted handles at call sites
        self.jitted_names: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and isinstance(node.value,
                                                           ast.Call):
                if _is_jax_jit_call(node.value):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            self.jitted_names.add(t.id)
                        elif isinstance(t, ast.Attribute):
                            self.jitted_names.add(t.attr)

    def finding(self, rule: str, node: ast.AST, message: str,
                fixit: str) -> Finding:
        return Finding(
            rule=rule,
            path=self.path,
            line=getattr(node, "lineno", 1),
            col=getattr(node, "col_offset", 0),
            message=message,
            fixit=fixit,
        )


def _rule_sky001(ctx: _Ctx) -> List[Finding]:
    """Host-device sync inside a hot path.

    Inside hot functions (per-step / per-microbatch code), a ``.item()``,
    ``jax.device_get``, or a ``float()``/``int()``/``np.asarray`` applied
    to an array-tainted value blocks the host on the device queue
    mid-issue.  ``float()``/``int()``/``np.asarray`` on plain host values
    (config dicts, counters) is NOT a sync, so those are only flagged
    when the argument derives from a dispatch-looking call (``jax.*``, a
    jitted handle, ``.apply``/``train_step``-style callees).  Syncs that
    occur lexically AFTER a ``block_until_ready`` in the same function
    are exempt: the queue is already drained, reading is free (the
    once-per-step loss readback idiom).
    """
    out: List[Finding] = []
    for fn, hot in _walk_functions(ctx.tree):
        if not hot:
            continue
        first_block_line = None
        for node in _own_nodes(fn):
            if isinstance(node, ast.Call) and \
                    _call_tail(node) == "block_until_ready":
                line = node.lineno
                if first_block_line is None or line < first_block_line:
                    first_block_line = line

        def is_dispatchy_call(call: ast.Call) -> bool:
            dotted = _dotted(call.func) or ""
            tail = _call_tail(call)
            if dotted.startswith(("jax.", "jnp.")) and \
                    not dotted.startswith(("jax.tree_util", "jax.tree")):
                return True
            return tail in ctx.jitted_names or \
                bool(_DISPATCHY_TAIL_RE.match(tail))

        # names assigned (directly or via one hop) from dispatch-looking
        # calls — the values that are plausibly jax.Arrays
        tainted: Set[str] = set()
        for _pass in range(2):
            for node in _own_nodes(fn):
                if not isinstance(node, ast.Assign):
                    continue
                v = node.value
                is_tainted = (
                    isinstance(v, ast.Call) and is_dispatchy_call(v)
                ) or any(
                    isinstance(n, ast.Name) and n.id in tainted
                    for n in ast.walk(v)
                )
                if is_tainted:
                    tainted |= set(_assign_target_names(node))

        def arg_is_arraylike(expr: ast.AST) -> bool:
            for n in ast.walk(expr):
                if isinstance(n, ast.Name) and n.id in tainted:
                    return True
                if isinstance(n, ast.Call) and (
                        is_dispatchy_call(n) or
                        (_dotted(n.func) or "") in _SYNC_NP_FNS):
                    return True
            return False

        for node in _own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            # >=: `float(jax.block_until_ready(loss))` drains the queue
            # on the sync's own line — the canonical one-line drained
            # read must not be flagged
            if first_block_line is not None and \
                    node.lineno >= first_block_line:
                continue
            dotted = _dotted(node.func)
            tail = _call_tail(node)
            hit = None
            if tail in _SYNC_ATTR_TAILS and isinstance(node.func,
                                                       ast.Attribute) \
                    and not node.args:
                hit = f".{tail}()"
            elif dotted == "jax.device_get":
                hit = dotted
            elif dotted in _SYNC_NP_FNS and node.args and \
                    arg_is_arraylike(node.args[0]):
                hit = dotted
            elif isinstance(node.func, ast.Name) and \
                    node.func.id in _SYNC_CALL_NAMES and node.args and \
                    not isinstance(node.args[0], ast.Constant) and \
                    arg_is_arraylike(node.args[0]):
                hit = f"{node.func.id}(...)"
            if hit:
                out.append(ctx.finding(
                    "SKY001", node,
                    f"{hit} in hot path `{getattr(fn, 'name', '?')}` "
                    f"forces a device->host sync mid-dispatch",
                    "move the read after the step's block_until_ready, "
                    "keep the value on device, or log asynchronously",
                ))
    return out


def _rule_sky002(ctx: _Ctx) -> List[Finding]:
    """Recompile hazards.

    (a) ``jax.jit(...)`` evaluated inside a loop or a hot function: each
    evaluation is a FRESH callable with an empty trace cache, so every
    step retraces and recompiles.  (b) branching (``if``/``while``) on a
    parameter of a ``@jax.jit``-decorated function: the tracer cannot
    evaluate a Python bool of a traced value (or, with concrete
    branching via static args, every new value recompiles).  (c)
    ``static_argnums``/``static_argnames`` given non-int/non-str
    values — unhashable or nonsensical static specs fail at call time.
    """
    out: List[Finding] = []
    # (a) jit created per call
    loop_spans: List[Tuple[int, int]] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.For, ast.While)):
            loop_spans.append((node.lineno, node.end_lineno or node.lineno))
    hot_fns = [fn for fn, hot in _walk_functions(ctx.tree) if hot]
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and _is_jax_jit_call(node)):
            continue
        in_loop = any(a <= node.lineno <= b for a, b in loop_spans)
        owner = next(
            (fn for fn in hot_fns
             if fn.lineno <= node.lineno <= (fn.end_lineno or fn.lineno)),
            None,
        )
        if in_loop or owner is not None:
            where = (
                "inside a loop" if in_loop
                else f"inside hot path `{owner.name}`"
            )
            out.append(ctx.finding(
                "SKY002", node,
                f"jax.jit(...) evaluated {where}: every evaluation is a "
                f"fresh callable that retraces and recompiles",
                "hoist the jit to module/init scope and reuse the handle",
            ))
        # (c) static spec sanity
        for kw in node.keywords:
            if kw.arg == "static_argnums":
                bad = _non_int_static(kw.value)
                if bad:
                    out.append(ctx.finding(
                        "SKY002", kw.value,
                        f"static_argnums must be ints, got {bad}",
                        "pass a tuple of int positions",
                    ))
            elif kw.arg == "static_argnames":
                bad = _non_str_static(kw.value)
                if bad:
                    out.append(ctx.finding(
                        "SKY002", kw.value,
                        f"static_argnames must be strings, got {bad}",
                        "pass a tuple of parameter-name strings",
                    ))
    # (b) traced branching inside @jax.jit functions
    for fn, _hot in _walk_functions(ctx.tree):
        if not _has_jit_decorator(fn):
            continue
        params = {
            a.arg for a in list(fn.args.args) + list(fn.args.kwonlyargs)
        }
        static = _static_param_names(fn)
        params -= static
        for node in _own_nodes(fn):
            if isinstance(node, (ast.If, ast.While)):
                used = {
                    n.id for n in ast.walk(node.test)
                    if isinstance(n, ast.Name)
                    and isinstance(n.ctx, ast.Load)
                }
                traced = sorted(used & params)
                if traced:
                    out.append(ctx.finding(
                        "SKY002", node,
                        f"Python branch on traced value(s) "
                        f"{', '.join(traced)} inside jitted "
                        f"`{fn.name}`",
                        "use jax.lax.cond/select, or mark the argument "
                        "static (each distinct value then recompiles)",
                    ))
    return out


def _non_int_static(value: ast.AST) -> Optional[str]:
    elems = value.elts if isinstance(value, (ast.Tuple, ast.List)) \
        else [value]
    for e in elems:
        if isinstance(e, ast.Constant):
            if not isinstance(e.value, int) or isinstance(e.value, bool):
                return repr(e.value)
        elif isinstance(e, (ast.Dict, ast.Set, ast.ListComp)):
            return type(e).__name__
    return None


def _non_str_static(value: ast.AST) -> Optional[str]:
    elems = value.elts if isinstance(value, (ast.Tuple, ast.List)) \
        else [value]
    for e in elems:
        if isinstance(e, ast.Constant) and not isinstance(e.value, str):
            return repr(e.value)
        if isinstance(e, (ast.Dict, ast.Set)):
            return type(e).__name__
    return None


def _has_jit_decorator(fn) -> bool:
    for dec in fn.decorator_list:
        if _dotted(dec) in ("jax.jit", "jit"):
            return True
        if isinstance(dec, ast.Call):
            if _dotted(dec.func) in ("jax.jit", "jit"):
                return True
            # functools.partial(jax.jit, ...)
            if _call_tail(dec) == "partial" and dec.args and \
                    _dotted(dec.args[0]) in ("jax.jit", "jit"):
                return True
    return False


def _static_param_names(fn) -> Set[str]:
    """Names marked static via a partial(jax.jit, static_argnames=...)."""
    names: Set[str] = set()
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        for kw in dec.keywords:
            if kw.arg == "static_argnames":
                elems = kw.value.elts if isinstance(
                    kw.value, (ast.Tuple, ast.List)) else [kw.value]
                for e in elems:
                    if isinstance(e, ast.Constant) and \
                            isinstance(e.value, str):
                        names.add(e.value)
            if kw.arg == "static_argnums":
                elems = kw.value.elts if isinstance(
                    kw.value, (ast.Tuple, ast.List)) else [kw.value]
                params = [a.arg for a in fn.args.args]
                for e in elems:
                    if isinstance(e, ast.Constant) and \
                            isinstance(e.value, int) and \
                            0 <= e.value < len(params):
                        names.add(params[e.value])
    return names


def _rule_sky003(ctx: _Ctx) -> List[Finding]:
    """PRNG discipline.

    (a) the same key Name fed to two streams of one ``rngs`` dict (e.g.
    ``{"params": rng, "dropout": rng}``) correlates the streams; (b) a
    ``jax.random.split`` result that is never read is a dead split —
    usually the caller meant to thread it (splits inside loops count the
    whole loop body as live range, so ``rng, sub = split(rng)`` threading
    is clean); (c) reading the ORIGINAL key after splitting it re-uses
    entropy the split already consumed — except via
    ``jax.random.fold_in(key, n)``, the sanctioned derive-a-sibling
    idiom.
    """
    out: List[Finding] = []
    # (a) duplicate key names in an rngs-style dict argument
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        tail = _call_tail(node)
        candidate_dicts: List[ast.Dict] = []
        if tail in ("init", "apply"):
            candidate_dicts += [a for a in node.args
                                if isinstance(a, ast.Dict)]
        candidate_dicts += [
            kw.value for kw in node.keywords
            if kw.arg == "rngs" and isinstance(kw.value, ast.Dict)
        ]
        for d in candidate_dicts:
            names = [v.id for v in d.values if isinstance(v, ast.Name)]
            dupes = sorted({n for n in names if names.count(n) > 1})
            keys_ok = any(
                isinstance(k, ast.Constant) and isinstance(k.value, str)
                for k in d.keys if k is not None
            )
            if dupes and keys_ok:
                out.append(ctx.finding(
                    "SKY003", d,
                    f"PRNG key `{dupes[0]}` reused across streams of one "
                    f"rngs dict — the streams are perfectly correlated",
                    "jax.random.split the key and give each stream its "
                    "own half",
                ))
    # (b)+(c) per-function split bookkeeping
    for fn, _hot in _walk_functions(ctx.tree):
        split_assigns = []  # (line, targets, src_key_name_or_None, node)
        for node in _own_nodes(fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call) and \
                    _dotted(node.value.func) == "jax.random.split":
                targets = _assign_target_names(node)
                src = node.value.args[0] if node.value.args else None
                src_name = src.id if isinstance(src, ast.Name) else None
                split_assigns.append((node.lineno, targets, src_name, node))
        if not split_assigns:
            continue
        # loads/stores over the WHOLE subtree, nested defs included: a
        # key consumed only via closure (`def inner(): ...normal(k1...)`,
        # the dominant JAX idiom) is a real use, and _own_nodes would
        # miss it — flagging valid closure code would break the --strict
        # CI gate.  Split ASSIGNMENTS stay _own_nodes-scoped (nested
        # functions get their own analysis pass via _walk_functions).
        loads: Dict[str, List[int]] = {}
        stores: Dict[str, List[int]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Name):
                (loads if isinstance(node.ctx, ast.Load)
                 else stores).setdefault(node.id, []).append(node.lineno)
        # loads that are the first argument of jax.random.fold_in are the
        # sanctioned derive-don't-consume idiom — never "stale reuse"
        fold_in_loads: Dict[str, Set[int]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    _dotted(node.func) == "jax.random.fold_in" and \
                    node.args and isinstance(node.args[0], ast.Name):
                fold_in_loads.setdefault(
                    node.args[0].id, set()
                ).add(node.args[0].lineno)
        loop_spans = [
            (n.lineno, n.end_lineno or n.lineno)
            for n in _own_nodes(fn) if isinstance(n, (ast.For, ast.While))
        ]
        for line, targets, src_name, node in split_assigns:
            # a split inside a loop is live across the back-edge: any
            # load anywhere in the loop body counts as a use
            spans = [(a, b) for a, b in loop_spans if a <= line <= b]
            live_from = min([a for a, _ in spans], default=line)
            for t in targets:
                if t.startswith("_"):
                    continue
                if t == src_name:
                    # `rng, sub = jax.random.split(rng)` — rebinding the
                    # source is the pattern SKY003(c)'s fixit recommends
                    # (and the loop back-edge consumes it); never "dead"
                    continue
                if not any(ln >= live_from and ln != line
                           for ln in loads.get(t, [])):
                    out.append(ctx.finding(
                        "SKY003", node,
                        f"split result `{t}` is never used (dead split)",
                        "thread the new key onward, or name it `_` if "
                        "the discard is deliberate",
                    ))
            if src_name and src_name not in targets:
                reassigned = [ln for ln in stores.get(src_name, [])
                              if ln > line]
                next_store = min(reassigned) if reassigned else None
                stale = [
                    ln for ln in loads.get(src_name, [])
                    if ln > line and (next_store is None or
                                      ln < next_store)
                    and ln not in fold_in_loads.get(src_name, set())
                ]
                if stale:
                    out.append(ctx.finding(
                        "SKY003", node,
                        f"key `{src_name}` is read on line {stale[0]} "
                        f"after being split on line {line} — stale key "
                        f"reuse",
                        "use one of the split halves, or rebind: "
                        f"`{src_name}, sub = jax.random.split("
                        f"{src_name})`",
                    ))
    return out


def _rule_sky004(ctx: _Ctx) -> List[Finding]:
    """Read of a buffer after it was donated.

    Tracks handles bound from ``jax.jit(fn, donate_argnums=...)`` (by
    Name or attribute tail) and flags a later read of a plain-Name
    argument that was passed in a donated position: on TPU/GPU the
    buffer is invalidated the moment the call dispatches, and the read
    returns garbage or raises — on CPU it silently "works", which is
    exactly why it ships.

    KNOWN LIMITATION: handles whose name is a ubiquitous method name
    (``update``/``apply``/``get``/``pop``/``add``) are NOT tracked —
    matching by tail would turn every ``some_dict.update(x)`` into a
    candidate.  Give donated handles distinctive names (the pipeline
    engine's ``bwd_donated``/``grad_add_donated`` convention) to keep
    them inside this rule's coverage.
    """
    donated: Dict[str, Tuple[int, ...]] = {}
    # tails that collide with ubiquitous dict/set methods would turn every
    # `d.update(x, y)` into a candidate — too generic to track by name
    generic_tails = {"update", "get", "pop", "add", "apply"}
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and _is_jax_jit_call(node.value):
            positions: List[int] = []
            for kw in node.value.keywords:
                if kw.arg != "donate_argnums":
                    continue
                elems = kw.value.elts if isinstance(
                    kw.value, (ast.Tuple, ast.List)) else [kw.value]
                for e in elems:
                    if isinstance(e, ast.Constant) and \
                            isinstance(e.value, int):
                        positions.append(e.value)
            if not positions:
                continue
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id not in generic_tails:
                    donated[t.id] = tuple(positions)
                elif isinstance(t, ast.Attribute) and \
                        t.attr not in generic_tails:
                    donated[t.attr] = tuple(positions)
    if not donated:
        return []
    out: List[Finding] = []
    for fn, _hot in _walk_functions(ctx.tree):
        events: Dict[str, List[Tuple[int, str]]] = {}
        for node in _own_nodes(fn):
            if isinstance(node, ast.Name):
                kind = "load" if isinstance(node.ctx, ast.Load) else "store"
                events.setdefault(node.id, []).append((node.lineno, kind))
        for node in _own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            tail = _call_tail(node)
            if tail not in donated:
                continue
            for pos in donated[tail]:
                if pos >= len(node.args):
                    continue
                arg = node.args[pos]
                if not isinstance(arg, ast.Name):
                    continue
                # a store ON the call's line is the assignment target
                # rebinding to the output (RHS evaluates first) — the
                # canonical safe pattern, so it counts as reassignment
                stores_after = [
                    ln for ln, kind in events.get(arg.id, [])
                    if kind == "store" and ln >= node.lineno
                ]
                cutoff = min(stores_after) if stores_after else None
                later_loads = [
                    ln for ln, kind in events.get(arg.id, [])
                    if kind == "load" and ln > node.lineno
                    and (cutoff is None or ln < cutoff)
                ]
                if later_loads:
                    out.append(ctx.finding(
                        "SKY004", node,
                        f"`{arg.id}` is read on line {later_loads[0]} "
                        f"after being donated to `{tail}` (position "
                        f"{pos}) — the buffer is invalid once the call "
                        f"dispatches",
                        "use the call's output, re-materialize the "
                        "value, or call the undonated twin",
                    ))
    return out


def _rule_sky005(ctx: _Ctx) -> List[Finding]:
    """Timing a dispatch region without blocking.

    ``t0 = perf_counter(); <jax work>; dt = perf_counter() - t0`` with no
    ``block_until_ready`` between measures DISPATCH, not compute — async
    dispatch returns in microseconds while the device still churns.
    Regions whose elapsed lands in a name containing ``dispatch`` are
    exempt: measuring host-issue time is this repo's one legitimate
    unblocked-timing idiom (``PipelineStats.dispatch_s``).
    """
    out: List[Finding] = []
    time_fns = {"time.perf_counter", "time.time", "time.monotonic",
                "perf_counter", "monotonic"}
    for fn, _hot in _walk_functions(ctx.tree):
        timer_vars: Dict[str, List[int]] = {}
        for node in _own_nodes(fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call) and \
                    _dotted(node.value.func) in time_fns:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        timer_vars.setdefault(t.id, []).append(node.lineno)
        if not timer_vars:
            continue
        calls = [n for n in _own_nodes(fn) if isinstance(n, ast.Call)]

        def classify(call: ast.Call) -> str:
            dotted = _dotted(call.func) or ""
            tail = _call_tail(call)
            if tail in _SYNCING_TAILS:
                return "sync"
            if tail in ctx.jitted_names:
                return "dispatch"
            if dotted in _NON_DISPATCH_JAX:
                return "host"
            if dotted.startswith(("jax.", "jnp.")) and \
                    not dotted.startswith(("jax.tree_util", "jax.tree")):
                return "dispatch"
            if _DISPATCHY_TAIL_RE.match(tail):
                return "dispatch"
            return "host"

        for node in _own_nodes(fn):
            if not (isinstance(node, ast.BinOp) and
                    isinstance(node.op, ast.Sub)):
                continue
            right = node.right
            if not (isinstance(right, ast.Name) and
                    right.id in timer_vars):
                continue
            left_ok = (
                isinstance(node.left, ast.Call) and
                _dotted(node.left.func) in time_fns
            ) or (
                isinstance(node.left, ast.Name) and
                node.left.id in timer_vars
            )
            if not left_ok:
                continue
            if "dispatch" in right.id:
                continue
            # elapsed stored into a dispatch-named target?  Scan the
            # FULL enclosing statement's source span (a wrapped
            # assignment puts the target name on a different line than
            # the BinOp), comments stripped
            stmts = [
                s for s in _own_nodes(fn)
                if isinstance(s, (ast.Assign, ast.AugAssign,
                                  ast.AnnAssign, ast.Return, ast.Expr))
                and s.lineno <= node.lineno <= (s.end_lineno or s.lineno)
            ]
            if stmts:
                stmt = max(stmts, key=lambda s: s.lineno)  # innermost
                span = ctx.lines[stmt.lineno - 1:stmt.end_lineno or
                                 stmt.lineno]
            else:
                span = ctx.lines[node.lineno - 1:node.lineno]
            if any("dispatch" in ln.split("#")[0] for ln in span):
                continue
            starts = [ln for ln in timer_vars[right.id]
                      if ln < node.lineno]
            if not starts:
                continue
            start = max(starts)
            region_calls = [c for c in calls
                            if start < c.lineno <= node.lineno]
            kinds = {classify(c) for c in region_calls}
            if "dispatch" in kinds and "sync" not in kinds:
                out.append(ctx.finding(
                    "SKY005", node,
                    f"elapsed-time of `{right.id}` (started line "
                    f"{start}) spans dispatching calls with no "
                    f"block_until_ready — this times async dispatch, "
                    f"not compute",
                    "jax.block_until_ready(result) before reading the "
                    "clock (or name the result *dispatch* if host-issue "
                    "time is the point)",
                ))
    return out


def _rule_sky006(ctx: _Ctx) -> List[Finding]:
    """Debug leftovers in library code."""
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            dotted = _dotted(node.func) or ""
            if dotted in ("jax.debug.print", "jax.debug.breakpoint",
                          "pdb.set_trace", "ipdb.set_trace") or \
                    (isinstance(node.func, ast.Name) and
                     node.func.id == "breakpoint"):
                out.append(ctx.finding(
                    "SKY006", node,
                    f"debug leftover `{dotted or 'breakpoint'}` in "
                    f"library code — it ships a host sync (or a wedge) "
                    f"into every dispatch",
                    "delete it, or gate it behind an explicit debug flag",
                ))
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            mods = [a.name for a in node.names]
            if isinstance(node, ast.ImportFrom) and node.module:
                mods.append(node.module)
            for m in mods:
                if m in ("pdb", "ipdb"):
                    out.append(ctx.finding(
                        "SKY006", node,
                        f"`import {m}` in library code",
                        "remove the debugger import before shipping",
                    ))
    return out


def _rule_sky007(ctx: _Ctx) -> List[Finding]:
    """Layer-config structure for the builder protocol.

    Every unit config handed to ``build_layer_stack`` /
    ``build_module_from_cfg`` must carry a ``layer_type`` key — the
    registry dispatches on it, and a missing key fails only at build
    time deep inside a launch path.
    """
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if _call_tail(node) not in ("build_layer_stack",
                                    "build_module_from_cfg"):
            continue
        if not node.args or not isinstance(node.args[0], ast.List):
            continue
        for elem in node.args[0].elts:
            ok = True
            if isinstance(elem, ast.Dict):
                keys = [k.value for k in elem.keys
                        if isinstance(k, ast.Constant)]
                has_splat = any(k is None for k in elem.keys)
                ok = "layer_type" in keys or has_splat
            elif isinstance(elem, ast.Call) and _call_tail(elem) == "dict":
                kws = [kw.arg for kw in elem.keywords]
                ok = "layer_type" in kws or None in kws
            if not ok:
                out.append(ctx.finding(
                    "SKY007", elem,
                    "layer config without a `layer_type` key — the "
                    "builder registry cannot dispatch it",
                    "add layer_type=<registered layer name> to the "
                    "config dict",
                ))
    return out


def _rule_sky008(ctx: _Ctx) -> List[Finding]:
    """Tuple-threading protocol: raw ``.apply`` results must pass
    through ``as_tuple`` before being star-unpacked.

    A layer's output is a tensor OR a tuple (``LayerStack`` threads
    whichever the layer returns); ``*out`` on a bare tensor iterates its
    leading axis — silently feeding batch slices to the next layer.
    """
    out: List[Finding] = []
    for fn, _hot in _walk_functions(ctx.tree):
        apply_results: Dict[str, int] = {}
        rewrapped: Dict[str, List[int]] = {}
        for node in _own_nodes(fn):
            if not isinstance(node, ast.Assign):
                continue
            v = node.value
            targets = _assign_target_names(node)
            if isinstance(v, ast.Call) and \
                    isinstance(v.func, ast.Attribute) and \
                    v.func.attr == "apply":
                for t in targets:
                    apply_results[t] = node.lineno
            elif isinstance(v, ast.Call) and _call_tail(v) == "as_tuple":
                for t in targets:
                    rewrapped.setdefault(t, []).append(node.lineno)
            else:
                for t in targets:
                    apply_results.pop(t, None)
        if not apply_results:
            continue
        for node in _own_nodes(fn):
            if not isinstance(node, ast.Starred):
                continue
            v = node.value
            if not isinstance(v, ast.Name):
                continue
            if v.id in apply_results and node.lineno > apply_results[v.id]:
                wraps = [ln for ln in rewrapped.get(v.id, [])
                         if apply_results[v.id] < ln <= node.lineno]
                if not wraps:
                    out.append(ctx.finding(
                        "SKY008", node,
                        f"`*{v.id}` star-unpacks a raw .apply() result "
                        f"(assigned line {apply_results[v.id]}) — a "
                        f"tensor output would iterate its batch axis",
                        f"thread `{v.id} = as_tuple({v.id})` first "
                        f"(builder.layer_stack.as_tuple)",
                    ))
    return out


RULES = {
    "SKY001": _rule_sky001,
    "SKY002": _rule_sky002,
    "SKY003": _rule_sky003,
    "SKY004": _rule_sky004,
    "SKY005": _rule_sky005,
    "SKY006": _rule_sky006,
    "SKY007": _rule_sky007,
    "SKY008": _rule_sky008,
}


# --------------------------------------------------------------------------
# engine
# --------------------------------------------------------------------------


def _suppressions(source: str):
    """(per-line {line: set|None}, file-level set).  None = all rules.

    Directives are read from real COMMENT tokens only (tokenize, not a
    raw line scan): a docstring or string literal that merely *mentions*
    the suppression syntax — documentation, test fixtures, this module's
    own docstring — must not silently disable rules and defeat the
    ``--strict`` gate.
    """
    import io
    import tokenize

    per_line: Dict[int, Optional[Set[str]]] = {}
    file_level: Set[str] = set()
    try:
        tokens = list(tokenize.generate_tokens(
            io.StringIO(source).readline
        ))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return per_line, file_level  # unparseable -> SKY000 anyway
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _SUPPRESS_FILE_RE.search(tok.string)
        if m:
            file_level |= {
                s.strip().upper() for s in m.group(1).split(",") if s.strip()
            }
            continue
        m = _SUPPRESS_LINE_RE.search(tok.string)
        if m:
            if m.group(1):
                per_line[tok.start[0]] = {
                    s.strip().upper()
                    for s in m.group(1).split(",") if s.strip()
                }
            else:
                per_line[tok.start[0]] = None  # all rules
    return per_line, file_level


def lint_source(source: str, path: str = "<string>",
                config: Optional[LintConfig] = None) -> List[Finding]:
    """Lint one source string; returns findings (suppressed ones only
    when the config asks for them)."""
    config = config or LintConfig()
    lines = source.splitlines()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        return [Finding(
            rule="SKY000", path=path, line=exc.lineno or 1,
            col=exc.offset or 0,
            message=f"file does not parse: {exc.msg}",
            fixit="fix the syntax error — unparseable files cannot be "
                  "linted and must not pass a lint gate",
        )]
    ctx = _Ctx(tree, path, lines)
    per_line, file_level = _suppressions(source)
    findings: List[Finding] = []
    for rule_id, rule_fn in RULES.items():
        if config.select is not None and rule_id not in config.select:
            continue
        if rule_id in config.ignore:
            continue
        for f in rule_fn(ctx):
            sup = rule_id in file_level
            line_sup = per_line.get(f.line, ...)
            if line_sup is None or (
                    line_sup is not ... and rule_id in line_sup):
                sup = True
            if sup:
                if config.include_suppressed:
                    findings.append(
                        dataclasses.replace(f, suppressed=True)
                    )
            else:
                findings.append(f)
    # stable order, dedup identical (rule, line, message) repeats
    seen = set()
    unique = []
    for f in sorted(findings, key=lambda f: (f.path, f.line, f.rule)):
        key = (f.rule, f.line, f.message)
        if key not in seen:
            seen.add(key)
            unique.append(f)
    return unique


def lint_file(path: str,
              config: Optional[LintConfig] = None) -> List[Finding]:
    try:
        with open(path, encoding="utf-8") as fh:
            source = fh.read()
    except (OSError, UnicodeDecodeError) as exc:
        # same contract as a syntax error: a file the gate cannot read
        # (non-UTF8, dangling symlink) must fail as SKY000, not crash
        # the linter mid-run with a raw traceback
        return [Finding(
            rule="SKY000", path=path, line=1, col=0,
            message=f"file cannot be read: {exc}",
            fixit="fix the encoding or the path — unreadable files "
                  "cannot be linted and must not pass a lint gate",
        )]
    return lint_source(source, path, config)


def lint_paths(paths: Sequence[str],
               config: Optional[LintConfig] = None) -> List[Finding]:
    """Lint files and/or directory trees.

    Directories are walked for ``*.py`` (caches skipped); an explicitly
    named FILE is always linted regardless of extension — a mistyped
    gate target must fail loudly (SKY000 on an unparseable file), not
    report clean.
    """
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, names in os.walk(p):
                dirs[:] = [d for d in dirs
                           if d not in ("__pycache__", ".git")]
                files += [os.path.join(root, n) for n in sorted(names)
                          if n.endswith(".py")]
        else:
            files.append(p)
    out: List[Finding] = []
    for f in sorted(set(files)):
        out += lint_file(f, config)
    return out


__all__ = ["Finding", "LintConfig", "RULES", "lint_source", "lint_file",
           "lint_paths"]
