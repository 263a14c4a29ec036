"""Draft-model speculative decoding for the paged serving engine.

Speculative decoding breaks the one-token-per-forward bound of
autoregressive decode: a cheap **draft** model proposes ``k`` tokens
autoregressively, then the **target** model verifies all ``k + 1``
positions in ONE batched forward (``models/gpt.apply_kv_paged`` at
``Lq = k + 1`` — the same program shape discipline as bucketed
prefill, so accept/reject is recompile-free).  On the greedy path the
committed stream is token-identical to non-speculative decoding *by
construction*: the target's own argmax at every position is what
commits; the draft only decides how many of those positions one tick
may confirm at once.

The draft here is a **prefix layer slice sharing the target's params**
(``models/gpt.draft_slice_indices``): embeddings + the first
``draft_blocks`` transformer blocks + the LM head.  Because the slice
is a prefix, the hidden states entering its layers are exactly the
target's, so the draft's KV cache for those layers IS the target's
stage-0 page slabs:

- **no draft prefill** — the target's prefill already wrote the pages
  the draft reads;
- **no extra KV memory** — the draft appends speculative KV into the
  same granted pages (within the request's reserved span, so the page
  allocator's worst-case charge already covers it: *grant-for-k* is
  free);
- **rollback is a watermark truncate** — a rejected draft token's KV
  sits beyond the request's committed ``index``, exactly like the pad
  tail of a bucketed prefill: masked by ``decode_visibility``,
  overwritten by the next committed write, refcounts untouched.  The
  verify forward itself rewrites the accepted positions' KV for the
  draft's layers (same params, same inputs), so draft-written state
  never outlives a tick.

The only resident cost is a copy of the LM-head (+ final LayerNorm)
params on the draft's device when the head lives on another stage —
``extra_param_mb`` reports it and the engine charges it in the
pre-flight (``analysis/plan_check`` ``serving.draft_mb``).
"""

from __future__ import annotations

import json
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..models.gpt import apply_kv_paged, attn_indices
from ..parallel.pipeline import _donation_enabled

# Process-level draft-program cache, the engine's _STAGE_PROGRAMS twin:
# jax's compile cache keys on function identity, so same-config drafts
# (fleet replica re-forms, test engines) must share one closure to
# restart at cache-hit speed.
_DRAFT_PROGRAMS: Dict[str, Any] = {}


def greedy_accept_count(
    draft_tokens: Sequence[int], target_tokens: Sequence[int]
) -> int:
    """Accepted draft prefix length under greedy verification: the
    longest prefix where the draft's proposal equals the target's own
    argmax at that position.  Pure host logic — the whole accept/
    commit/rollback decision, unit-testable without a model."""
    n = 0
    for d, t in zip(draft_tokens, target_tokens):
        if int(d) != int(t):
            break
        n += 1
    return n


def tree_param_mb(params) -> float:
    """Total MB of a param tree (the pre-flight charge for the draft's
    device-resident head copy)."""
    leaves = jax.tree_util.tree_leaves(params)
    return float(
        sum(np.prod(l.shape) * l.dtype.itemsize for l in leaves)
        / 1024.0 ** 2
    )


class DraftModel:
    """The drafting half of speculative decoding: a prefix slice of the
    target (embeddings + ``draft_blocks`` blocks + LM head) compiled as
    one ``Lq = 1`` paged decode program on the target's FIRST stage
    device, reading and writing the first ``draft_blocks`` pairs of
    that stage's page slabs.

    ``modules``/``params`` are the already-sliced lists (the engine
    slices the full stack with ``models/gpt.draft_slice_indices`` and
    device-puts the head's params); ``extra_param_mb`` is the resident
    memory this draft ADDS to the device (0 when the head already lives
    there — the single-stage engine).
    """

    def __init__(
        self,
        modules: Sequence[Any],
        params: Sequence[Any],
        device,
        *,
        extra_param_mb: float = 0.0,
        program_key: Optional[str] = None,
        attn_impl: str = "xla",
    ):
        self.modules = list(modules)
        self.params = list(params)
        self.device = device
        self.num_attn = len(attn_indices(self.modules))
        if self.num_attn < 1:
            raise ValueError(
                "draft slice carries no attention unit — nothing to "
                "draft with"
            )
        self.extra_param_mb = float(extra_param_mb)
        self.attn_impl = attn_impl
        cached = (
            _DRAFT_PROGRAMS.get(program_key)
            if program_key is not None else None
        )
        if cached is not None:
            self._step_donated, self._loop_donated = cached
            return
        mods = self.modules
        impl = attn_impl

        def spec_draft_step(params_list, tokens, slabs, tables, index,
                            valid_len):
            # argmax FUSED into the program: drafting is greedy by
            # definition (only the target's verify logits ever commit
            # a token), so the draft never needs its logits on the
            # host — one jit call per draft step, token ids in, token
            # ids out, no per-step device->host sync
            out, new_slabs = apply_kv_paged(
                mods, params_list, tokens[:, None], slabs, tables,
                index, valid_len, attn_impl=impl,
            )
            nxt = jnp.argmax(out[:, 0], axis=-1).astype(jnp.int32)
            return nxt, new_slabs

        def spec_draft_loop(params_list, tokens, slabs, tables, index,
                            reserve, k):
            # the WHOLE k-step autoregressive draft as ONE compiled
            # program (k static, unrolled): per-step dispatch cost was
            # measured at ~half a full decode tick on the CPU fallback
            # — paying it k times per speculative tick ate most of the
            # speculation win.  One dispatch per tick drafts all k.
            cur = tokens
            proposals = []
            for j in range(k):
                idx = index + j
                valid = jnp.minimum(idx + 1, reserve)
                cur, slabs = spec_draft_step(
                    params_list, cur, slabs, tables, idx, valid
                )
                proposals.append(cur)
            return jnp.stack(proposals, axis=1), slabs

        if _donation_enabled():
            self._step_donated = jax.jit(spec_draft_step,
                                         donate_argnums=(2,))
            self._loop_donated = jax.jit(
                spec_draft_loop, static_argnums=(6,), donate_argnums=(2,)
            )
        else:
            self._step_donated = jax.jit(spec_draft_step)
            self._loop_donated = jax.jit(spec_draft_loop,
                                         static_argnums=(6,))
        if program_key is not None:
            _DRAFT_PROGRAMS[program_key] = (
                self._step_donated, self._loop_donated
            )

    @staticmethod
    def program_key(
        draft_cfgs: Sequence[Dict], max_len: int,
        attn_impl: str = "xla", kv_dtype=None,
    ) -> str:
        """Cache key: the sliced layer configs + cache depth + donation
        + the attention impl / KV storage dtype (both change traced
        code) — the engine's stage program-key recipe, draft flavored."""
        return json.dumps(
            ["draft", list(draft_cfgs), int(max_len),
             bool(_donation_enabled()), str(attn_impl), str(kv_dtype)],
            sort_keys=True, default=str,
        )

    def decode_step(self, tokens, slabs, tables, index, valid_len):
        """One draft step: ``tokens`` [rows] int32 in, next greedy
        ``tokens`` [rows] out (a DEVICE array — feed it straight back
        for the next step; the engine hosts it once after the loop).
        ``slabs`` must be exactly the first ``num_attn`` (k, v) pairs
        of the target's stage-0 slabs; the caller rebinds the stage's
        slab prefix to ``new_slabs`` (donation discipline, same as
        every stage program)."""
        if len(slabs) != self.num_attn:
            raise ValueError(
                f"draft needs {self.num_attn} slab pairs, got "
                f"{len(slabs)}"
            )
        # donation discipline: the donated handle is rebound by the
        # same statement that consumes it (the engine's slab idiom)
        nxt, slabs = self._step_donated(self.params, tokens, slabs,
                                        tables, index, valid_len)
        return nxt, slabs

    def draft_k(self, tokens, slabs, tables, index, reserve, k):
        """The whole ``k``-token autoregressive draft in ONE dispatch:
        ``tokens`` [rows] (each row's last committed token) in,
        proposals [rows, k] out, with per-step writes capped at
        ``reserve`` (the rows' page reservations).  ``k`` is a static
        shape argument — one compiled program per (rows, k), the same
        discipline as the verify forward's ``Lq = k + 1``."""
        if len(slabs) != self.num_attn:
            raise ValueError(
                f"draft needs {self.num_attn} slab pairs, got "
                f"{len(slabs)}"
            )
        proposals, slabs = self._loop_donated(
            self.params, tokens, slabs, tables, index, reserve, int(k)
        )
        return proposals, slabs


__all__ = ["DraftModel", "greedy_accept_count", "tree_param_mb"]
