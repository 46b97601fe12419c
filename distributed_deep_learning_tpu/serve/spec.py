"""Speculative decoding: draft proposes, target verifies, parity holds.

Plain decode is one forward per token — memory-bound at batch 1, the
third bottleneck the ROADMAP names.  Speculative decoding buys back
arithmetic intensity: a cheap DRAFT model proposes ``k`` tokens
autoregressively, then the full target model scores all ``k+1``
positions in ONE batched cached forward (through the same
``cached_apply`` seam decode uses) and keeps the longest prefix of
proposals that matches its own greedy choices.

Greedy parity is exact, not approximate.  Let the committed stream be
``x_0..x_{c-1}`` with pending token ``t``.  The verify forward feeds
``[t, d_0 .. d_{k-1}]`` and yields target argmaxes ``g_0..g_k`` where
``g_j`` conditions on the committed stream plus ``d_0..d_{j-1}``.  By
induction, as long as every earlier draft token matched (``d_i = g_i``),
``g_j`` conditions on exactly the target's own greedy stream — so
emitting ``g_0..g_a`` (``a`` = leading-match count) emits precisely the
tokens plain greedy decode would have produced, one extra "bonus"
correction token included.  Acceptance rate only changes SPEED, never
one output token — which is what lets the tests assert bit-identical
outputs against ``generate()`` while counting fewer target forwards.

The draft here is a TRUNCATED view of the target itself: its first
``draft_layers`` transformer layers plus the (tied) embedding and final
norm, sharing the trained parameter arrays — no second training run, no
extra memory beyond the draft's own KV pool.  Any ``CausalLM`` with the
same vocab works as a draft; truncation is just the zero-cost default.

The draft runs ``k+1`` cached steps per round (not ``k``): the last
step feeds ``d_{k-1}`` to write draft KV at position ``c+k`` whose
proposal is discarded.  Without it, an all-accept round would leave a
hole at ``c+k`` in the draft's cache — the next round starts feeding at
``c+k+1`` and KV holes, unlike garbage-above-the-counter, are never
overwritten.
"""

from __future__ import annotations

import numpy as np


def truncated_draft(decode_model, params, draft_layers: int):
    """A draft ``CausalLM`` sharing the target's weights: first
    ``draft_layers`` layers + embedding + final norm (the logit head is
    the tied embedding, so it comes along for free).  Returns
    ``(draft_model, draft_params)``; the arrays are the target's own —
    zero parameter memory cost."""
    n = decode_model.num_layers
    if not 1 <= draft_layers < n:
        raise ValueError(
            f"draft_layers must be in [1, {n - 1}], got {draft_layers}")
    layers = decode_model.layers
    draft = decode_model.clone(
        num_layers=draft_layers,
        layers=None if layers is None else layers[:draft_layers])
    # accept either flavor: the engine's inner param dict (module names
    # at top level) or the full {"params": ...} variable dict
    wrapped = "params" in params and "embed" not in params
    src = params["params"] if wrapped else params
    keep = {name: src[name] for name in ("embed", "final_norm", "head")
            if name in src}        # "head": an untied model's own table
    for i in range(draft_layers):
        keep[f"layer_{i}"] = src[f"layer_{i}"]
    return draft, ({"params": keep} if wrapped else keep)


def greedy_accept(proposed, verified):
    """Host acceptance: longest matching prefix, plus the correction.

    ``proposed`` — the draft's ``k`` tokens ``d_0..d_{k-1}``.
    ``verified`` — the target's ``k+1`` greedy tokens ``g_0..g_k`` from
    the batched verify forward.  Returns ``(a, emitted)`` where ``a`` is
    the accepted-proposal count and ``emitted`` the ``a+1`` tokens to
    append to the stream (``g_0..g_a``; since ``d_j = g_j`` for
    ``j < a``, these ARE the accepted drafts plus the target's
    correction — or bonus token when everything matched)."""
    proposed = np.asarray(proposed)
    verified = np.asarray(verified)
    k = len(proposed)
    if len(verified) != k + 1:
        raise ValueError(
            f"verified must have k+1={k + 1} tokens, got {len(verified)}")
    a = 0
    while a < k and int(proposed[a]) == int(verified[a]):
        a += 1
    return a, [int(t) for t in verified[:a + 1]]
