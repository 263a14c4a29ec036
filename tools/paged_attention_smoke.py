#!/usr/bin/env python
"""CI smoke for the fused paged-attention kernel (interpret mode).

Loads ``ops/paged_attention.py`` and pins the Pallas kernel against its
own XLA reference on the contract's edge cases: a sequence crossing a
page boundary, sentinel-padded table entries, a single row and a full
wave, decode (``Lq=1``) and speculative-verify (``Lq=k+1``) shapes, and
the int8 dequant variant (bounded error vs the fp math).  Structural
drift in the kernel's masking/accumulation fails the job.

Unlike the pure-stdlib smokes (``paging_smoke``/``chunk_smoke``), this
gate needs jax: on a bare lint runner (no jax installed) it prints a
SKIP and exits 0 — the pytest suite (``tests/test_paged_attention.py``)
covers the same contract wherever jax exists, so the skip loses no
coverage, only latency-to-signal on jax-equipped runners.

Usage::

    python tools/paged_attention_smoke.py
"""

from __future__ import annotations

import importlib.util
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_by_path(name: str, *parts: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, *parts)
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def check(cond, message):
    if not cond:
        print(f"FAIL: {message}")
        raise SystemExit(1)
    print(f"  ok: {message}")


def main() -> int:
    try:
        import jax  # noqa: F401
        import jax.numpy as jnp  # noqa: F401
        import numpy as np
    except Exception as exc:  # pragma: no cover - bare lint runner
        print(f"SKIP: jax unavailable ({exc}); the kernel smoke needs "
              f"an accelerator stack — tests/test_paged_attention.py "
              f"covers this contract where jax exists")
        return 0

    try:
        from skycomputing_tpu.ops import paged_attention as _pa
    except Exception:  # pragma: no cover - bare-runner fallback
        _pa = _load_by_path(
            "_skytpu_paged_attention_smoke",
            "skycomputing_tpu", "ops", "paged_attention.py",
        )

    rng = np.random.default_rng(0)
    P, ps, H, D = 10, 4, 2, 16

    def run_case(name, R, Lq, tables, index, quantized=False):
        q = rng.standard_normal((R, Lq, H, D)).astype(np.float32)
        if quantized:
            kq = rng.integers(-127, 128, (P, ps, H * D)).astype(np.int8)
            vq = rng.integers(-127, 128, (P, ps, H * D)).astype(np.int8)
            ks = rng.uniform(0.005, 0.03, (P, H)).astype(np.float32)
            vs = rng.uniform(0.005, 0.03, (P, H)).astype(np.float32)
            out = _pa.paged_attention(
                q, kq, vq, tables, index, k_scale=ks, v_scale=vs,
                interpret=True,
            )
            ref = _pa.paged_attention_reference(
                q, kq, vq, tables, index, k_scale=ks, v_scale=vs,
            )
        else:
            k = rng.standard_normal((P, ps, H * D)).astype(np.float32)
            v = rng.standard_normal((P, ps, H * D)).astype(np.float32)
            out = _pa.paged_attention(q, k, v, tables, index,
                                      interpret=True)
            ref = _pa.paged_attention_reference(q, k, v, tables, index)
        err = float(np.max(np.abs(np.asarray(out) - np.asarray(ref))))
        check(err < 1e-4, f"{name}: kernel == XLA reference "
                          f"(max |err| {err:.1e})")

    print("fused kernel vs XLA reference (interpret mode):")
    # one row, sequence crossing a page boundary (len 9 over ps=4)
    t = np.full((1, 3), P, np.int32)
    t[0, :3] = [7, 2, 5]
    run_case("1 row, page-boundary crossing", 1, 1, t,
             np.array([8], np.int32))
    # full wave, sentinel-padded tables, mixed lengths
    t = np.full((3, 5), P, np.int32)
    t[0, :3] = [7, 2, 5]
    t[1, :2] = [0, 9]
    t[2, :5] = [1, 3, 4, 6, 8]
    run_case("full wave, sentinel-padded tables", 3, 1, t,
             np.array([8, 4, 16], np.int32))
    # speculative-verify shape (Lq = k + 1)
    run_case("verify shape Lq=3", 3, 3, t, np.array([6, 2, 14], np.int32))
    # int8 dequant variant
    run_case("int8 dequant, full wave", 3, 1, t,
             np.array([8, 4, 16], np.int32), quantized=True)

    print("paged-attention smoke PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
