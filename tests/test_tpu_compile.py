"""Compile the main path's Pallas kernels for a TPU that is not attached.

Interpret mode (every other kernel test) checks a kernel's arithmetic; it
cannot see what the TPU lowering refuses — ``ops/paged_attention.py``
passed every interpret-mode test while its per-head block shape could
not be lowered at all.  The TPU compiler is installed wherever jax's TPU
support is, and compiles for a *described* ``v5e:2x2`` topology, so these
tests lower and compile each kernel at the widths ``chip_smoke.py`` runs
(BERT-large attention, GPT-2 paged decode and speculative verify) and
require a ``tpu_custom_call`` in the compiled program: a kernel lowered
in interpret mode has none.  Nothing runs, so nothing here is a device
result.  Skipped where the topology cannot be described.
"""

import os

import jax
import jax.numpy as jnp
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
    pages_ = on_v5e((pages, PAGE_SIZE, heads, HEAD_DIM), page_dtype)
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
