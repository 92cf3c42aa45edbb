"""The comparison that decides ``correct``: served tokens against the
plain float32 reference.

Once the window has closed and the program's state is freed, a sample
of the finished requests, drawn from the seed with the longest among
them, is run through the reference once each (prompt followed by its
served tokens). For every served token the gap by which its reference
logit lies below the reference's best at that position is read; the
compared number is the widest gap. That holds for greedy tokens, which
is all the traffic serves.

The control puts the reference computed in float8 e4m3 in the
program's place: at each position of the same prompts and tokens it
reads the gap of the token the float8 reference puts first.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def sample(finished: Dict[int, dict], seed: int, min_tokens: int) -> List[int]:
    """uids of the finished requests to compare: the longest (prompt and
    output), then others in an order drawn from the seed until
    ``min_tokens`` served tokens are covered."""
    if not finished:
        return []
    def length(u):
        return len(finished[u]["prompt"]) + len(finished[u]["tokens"])
    uids = sorted(finished)
    longest = max(uids, key=lambda u: (length(u), u))
    rest = [u for u in uids if u != longest]
    order = np.random.default_rng([seed, 1]).permutation(len(rest))
    picked, n = [longest], len(finished[longest]["tokens"])
    for i in order:
        if n >= min_tokens:
            break
        picked.append(rest[i])
        n += len(finished[rest[i]]["tokens"])
    return picked


def _positions(prompt: np.ndarray, tokens: np.ndarray, max_seq: int):
    """The padded input (prompt then served tokens but the last) and
    the positions whose logits chose each served token."""
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    if len(seq) > max_seq:
        raise ValueError(f"sequence of {len(seq)} exceeds max_seq {max_seq}")
    padded = np.zeros(max_seq, np.int32)
    padded[:len(seq)] = seq
    pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
    return padded, pos


def gaps(ref, sz: dict, params, reqs: List[dict], max_seq: int, *,
         control: bool = False) -> np.ndarray:
    """Per served token, the reference's best logit minus its logit for
    the served token (``control=False``) or for the float8 reference's
    first choice (``control=True``)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gap_of(logits, pos, tok):
        rows = logits[pos]
        return jnp.max(rows, axis=-1) - jnp.take_along_axis(rows, tok[:, None], axis=-1)[:, 0]

    out = []
    for r in reqs:
        padded, pos = _positions(r["prompt"], r["tokens"], max_seq)
        want = ref.forward(sz, params, jnp.asarray(padded))
        tok = np.asarray(r["tokens"], np.int32)
        if control:
            low = ref.forward(sz, params, jnp.asarray(padded), fp8=True)
            tok = np.asarray(jnp.argmax(low, axis=-1), np.int32)[pos]
            del low
        # fixed shapes: one compile whatever the request's length
        n = len(pos)
        pos_p = np.zeros(max_seq, np.int32)
        tok_p = np.zeros(max_seq, np.int32)
        pos_p[:n], tok_p[:n] = pos, tok
        out.append(np.asarray(gap_of(want, jnp.asarray(pos_p), jnp.asarray(tok_p)))[:n])
        del want
    return np.concatenate(out) if out else np.zeros(0)
