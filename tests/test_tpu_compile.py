"""Compile the main path's Pallas kernels for a TPU that is not attached.

Interpret mode (every other kernel test) checks a kernel's arithmetic; it
cannot see what the TPU lowering refuses — ``ops/paged_attention.py``
passed every interpret-mode test while its per-head block shape could
not be lowered at all.  The TPU compiler is installed wherever jax's TPU
support is, and compiles for a *described* ``v5e:2x2`` topology, so these
tests lower and compile each kernel at the widths ``chip_smoke.py`` runs
(BERT-large attention, GPT-2 paged decode and speculative verify) and
require a ``tpu_custom_call`` in the compiled program: a kernel lowered
in interpret mode has none.  The paged stage's whole step program is
compiled too, at GPT-2-large's pool, and its optimized HLO read for
what no CPU run shows: an operation that relays a whole slab out, and
code emitted anew for every layer.
Nothing runs, so nothing here is a device result.  Skipped where the
topology cannot be described.
"""

import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skycomputing_tpu.ops.flash_attention import flash_attention
from skycomputing_tpu.ops.paged_attention import paged_attention

# GPT-2 small attention geometry over the engine's default 16-token pages
ROWS, HEADS, HEAD_DIM, PAGES, PAGE_SIZE, WIDTH = 8, 12, 64, 256, 16, 64


@pytest.fixture(scope="module")
def on_v5e():
    """``shape -> ShapeDtypeStruct`` placed on one chip of a described
    v5e host, with the persistent compile cache off for the duration (a
    described-device executable can be written to the cache but never
    read back, so every later run would warn and recompile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu here: nothing to compile with
        pytest.skip(f"cannot describe a v5e topology: {exc!r}")
    chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=chip
    )
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_forward_compiles_at_bert_large(on_v5e):
    qkv = on_v5e((32, 128, 16, 64), jnp.bfloat16)
    bias = on_v5e((32, 128), jnp.float32)
    text = _compiled_text(
        lambda q, k, v, b: flash_attention(
            q, k, v, b, None, 256, 512, False
        ),
        qkv, qkv, qkv, bias,
    )
    assert "tpu_custom_call" in text


# (rows, query_len, heads, pages): chip_smoke.py's GPT-2 small server
# (decode, speculative verify) and the benchmark's GPT-2-large cell (a
# 32-row decode tick, its widest prefill bucket: query blocks tiled)
PAGED_SHAPES = {
    "decode": (ROWS, 1, HEADS, PAGES),
    "verify": (ROWS, 4, HEADS, PAGES),
    "large-decode": (32, 1, 20, 2048),
    "large-prefill768": (1, 768, 20, 2048),
}


@pytest.mark.parametrize("shape", list(PAGED_SHAPES))
@pytest.mark.parametrize("kv", ["fp", "int8"])
def test_paged_attention_compiles_at_gpt2_widths(on_v5e, kv, shape):
    rows, query_len, heads, pages = PAGED_SHAPES[shape]
    q = on_v5e((rows, query_len, heads, HEAD_DIM), jnp.bfloat16)
    page_dtype = jnp.int8 if kv == "int8" else jnp.bfloat16
    pages_ = on_v5e((pages, PAGE_SIZE, heads * HEAD_DIM), page_dtype)
    table = on_v5e((rows, WIDTH), jnp.int32)
    index = on_v5e((rows,), jnp.int32)
    if kv == "int8":
        scale = on_v5e((pages, heads), jnp.float32)
        text = _compiled_text(
            lambda q, k, v, t, i, ks, vs: paged_attention(
                q, k, v, t, i, k_scale=ks, v_scale=vs, interpret=False
            ),
            q, pages_, pages_, table, index, scale, scale,
        )
    else:
        text = _compiled_text(
            lambda q, k, v, t, i: paged_attention(
                q, k, v, t, i, interpret=False
            ),
            q, pages_, pages_, table, index,
        )
    assert "tpu_custom_call" in text
    # the name a device trace shows, which the benchmark's
    # ``paged_attn_pct.serve`` finds the kernel by (``paged``)
    assert "%decode_paged_attention" in text


# the benchmark's GPT-2-large pool: 2048 bf16 pages of 16, 20 heads x 64
LARGE = dict(pages=2048, heads=20, hidden=1280, width=64, blocks=2)
# (rows, query length): a 32-row decode tick; one request's prefill
# bucket
STEP_SHAPES = {"decode": (32, 1), "prefill256": (1, 256)}

_HLO_RESULT = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\("
)


def _paged_step(on_v5e, monkeypatch, blocks, pages, held=False):
    """The stage's step program as ``_ServingStage`` builds it
    (``apply_kv_paged``, the Pallas kernel) for a GPT of ``blocks``
    blocks at GPT-2-large's widths: ``(step, params, slab)``, the
    params abstract and on the described chip, each leaf in the dtype
    the engine holds it in where ``held``."""
    from skycomputing_tpu.builder import build_layer_stack
    from skycomputing_tpu.models.gpt import (
        GptConfig,
        apply_kv_paged,
        decode_modules,
        gpt_layer_configs,
        served_param_dtypes,
    )

    # on this CPU backend ``paged_attention`` would pick interpret mode
    kernel_module = sys.modules["skycomputing_tpu.ops.paged_attention"]
    monkeypatch.setattr(
        kernel_module, "paged_attention",
        functools.partial(paged_attention, interpret=False),
    )
    cfg = GptConfig(
        vocab_size=50257, hidden_size=LARGE["hidden"],
        num_hidden_layers=blocks,
        num_attention_heads=LARGE["heads"], intermediate_size=5120,
        max_position_embeddings=1024, dtype="bfloat16",
    )
    stack = build_layer_stack(gpt_layer_configs(cfg, deterministic=True))
    modules = decode_modules(stack)
    avals = jax.eval_shape(
        lambda key: stack.init(key, np.ones((1, 8), np.int32)),
        jax.random.key(0),
    )
    params = [
        jax.tree.map(
            lambda leaf, dtype: on_v5e(
                leaf.shape, dtype if held else leaf.dtype),
            p, served_param_dtypes(m, p),
        )
        for m, p in zip(modules, avals)
    ]
    slab = on_v5e((pages, PAGE_SIZE, LARGE["hidden"]), jnp.bfloat16)

    def step(params_list, data, slabs, tables, index, valid_len):
        return apply_kv_paged(
            modules, params_list, data, slabs, tables, index, valid_len,
            attn_impl="pallas",
        )

    return step, params, slab


def _step_args(on_v5e, slab, blocks, rows, query_len):
    return (
        on_v5e((rows, query_len), jnp.int32), [(slab, slab)] * blocks,
        on_v5e((rows, LARGE["width"]), jnp.int32),
        on_v5e((rows,), jnp.int32), on_v5e((rows,), jnp.int32),
    )


@pytest.mark.parametrize("shape", list(STEP_SHAPES))
def test_paged_step_never_relays_a_slab_out(on_v5e, monkeypatch, shape):
    """The stage's step program (``apply_kv_paged`` as
    ``_ServingStage`` jits it: slabs donated, the Pallas kernel)
    holds no ``copy``, ``transpose`` or ``reshape`` whose result is as
    large as a slab: the scatter's flat view and the kernel's operand
    are bitcasts of the stored ``[num_pages, page_size, heads *
    head_dim]`` pool.  Stored ``[.., heads, head_dim]`` the same
    program copied every slab twice and reshaped it once, 113 ms of a
    124 ms tick (PERF.md, PR 30).  Two blocks stand for the 36: every
    block's slabs are treated alike."""
    rows, query_len = STEP_SHAPES[shape]
    step, params, slab = _paged_step(on_v5e, monkeypatch, LARGE["blocks"],
                                     LARGE["pages"])
    text = jax.jit(step, donate_argnums=(2,)).lower(
        params, *_step_args(on_v5e, slab, LARGE["blocks"], rows, query_len),
    ).compile().as_text()
    assert text.count("%decode_paged_attention") >= LARGE["blocks"]
    slab_elements = int(np.prod(slab.shape))
    relayouts = []
    for line in text.splitlines():
        found = _HLO_RESULT.match(line)
        if found and found.group(2) in ("copy", "transpose", "reshape"):
            dims = [int(d) for d in found.group(1).split(",") if d]
            if int(np.prod(dims)) >= slab_elements:
                relayouts.append(line.strip()[:160])
    assert not relayouts, relayouts


def test_stage_program_code_is_emitted_once_for_its_layers(
        on_v5e, monkeypatch):
    """A stage program of the held weights, compiled with the options
    ``_ServingStage`` gives a TPU, holds its layers' code once: six
    blocks' code is within a tenth of two blocks'.  Without them each
    block adds its own ~2 MB (at 36 blocks a program held 36-108 MB of
    code, in device memory and in the persistent compile cache)."""
    from skycomputing_tpu.serving.engine import _stage_compiler_options

    def code_bytes(blocks, options):
        step, params, slab = _paged_step(on_v5e, monkeypatch, blocks,
                                         256, held=True)
        device = next(iter(slab.sharding.device_set))
        compiled = jax.jit(
            step, donate_argnums=(2,),
            compiler_options=(_stage_compiler_options(device)
                              if options else None),
        ).lower(params, *_step_args(on_v5e, slab, blocks, 1, 128)).compile()
        return compiled.memory_analysis().generated_code_size_in_bytes

    code = {(blocks, options): code_bytes(blocks, options)
            for blocks, options in ((2, True), (6, True), (6, False))}
    assert code[6, True] - code[2, True] < code[2, True] / 10, code
    assert code[6, False] > 2 * code[6, True], code
