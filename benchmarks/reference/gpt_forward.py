"""Plain reference for greedy decoding of the GPT stack: ONE full forward
(no cache, no kernel, no batching by the engine) over a finished stream,
and how far below the reference's best logit each emitted token sits.
(A copy of ``chip_smoke.py``'s ``make_argmax_gaps``, which passed on the
v5e in PR 21.)

Why logits and not tokens: the LM head emits bf16 logits over 50257
tokens, so the best two are often within an ulp or two of each other,
and two correct bf16 evaluations can rank them differently; after one
such flip the streams differ for good.  What is REQUIRED is that every
token the engine emitted is the reference's own argmax to within
``TIE_ULPS`` bf16 ulps of the top logit.  A wrong kernel or a wrong page
is off by the spread of the logits: hundreds of ulps.
"""

from __future__ import annotations

TIE_ULPS = 4


def make_argmax_gaps(stack, pad_to):
    """``(params, streams, prompt_lens) -> per stream, per generated
    token``: bf16 ulps of the top logit between the reference's best and
    the token emitted; 0 = its argmax."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def gaps(params, ids):
        logits = stack.apply(params, ids)[:, :-1]  # position t predicts t+1
        top = jnp.max(logits, axis=-1)
        chosen = jnp.take_along_axis(
            logits, ids[:, 1:, None], axis=-1
        )[..., 0]
        ulp = 2.0 ** (jnp.floor(jnp.log2(jnp.abs(top))) - 7)
        return (top - chosen) / ulp

    def per_stream(params, streams, prompt_lens):
        ids = np.zeros((len(streams), pad_to), np.int32)
        for row, stream in zip(ids, streams):
            row[: len(stream)] = stream
        table = np.asarray(gaps(params, jnp.asarray(ids)))
        return [
            table[i, n - 1: len(stream) - 1]
            for i, (stream, n) in enumerate(zip(streams, prompt_lens))
        ]

    return per_stream
