"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests it finished (drawn
from the seed, the longest always in it) is run once through the plain
reference: each prompt with its served tokens, teacher-forced. The number
compared is the widest gap, over every served token of the sample, by
which that token's reference logit lies below the reference's best. A
greedy server that is sound picks the reference's best token, or one the
reference all but ties with it (the program rounds to bfloat16 where the
reference keeps float32), so its gap stays small; a token altered anywhere
on the served path (prefill, the scatter into the pool, paged decode, a
join or a leave, a collective) lands some units below.

The control is the reference computed in the next precision below the
stated one, in the program's place: at the same positions of the same
sequences, the gap of the token that the lower precision puts first.
"""

from __future__ import annotations

import numpy as np


def choose(reqs, n: int, seed: int) -> list:
    """``n`` finished requests (fewer if fewer finished): the longest, then
    others drawn from the seed without replacement."""
    done = [r for r in reqs if r.ok]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.tokens), -r.submit_t))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC0]).permutation(len(rest))
    return [longest] + [rest[i] for i in rng[: max(n - 1, 0)]]


def pack(sample, seq_len: int, rows: int):
    """(tokens (S, seq_len), positions (S, rows), served (S, rows), mask)."""
    S = len(sample)
    tokens = np.zeros((S, seq_len), np.int32)
    pos = np.zeros((S, rows), np.int32)
    served = np.zeros((S, rows), np.int32)
    mask = np.zeros((S, rows), bool)
    for i, r in enumerate(sample):
        seq = list(r.prompt) + list(r.tokens[:-1])
        n = len(r.tokens)
        if len(seq) > seq_len or n > rows:
            raise ValueError(
                f"request of {len(seq)} positions / {n} tokens does not fit "
                f"the comparison's ({seq_len}, {rows})")
        tokens[i, : len(seq)] = seq
        pos[i, :n] = r.prompt_len - 1 + np.arange(n)
        pos[i, n:] = pos[i, n - 1]
        served[i, :n] = r.tokens
        served[i, n:] = r.tokens[-1]
        mask[i, :n] = True
    return tokens, pos, served, mask


def gaps(ref_logits, picked, mask):
    """Per position, how far the picked token's reference logit lies below
    the reference's best (>= 0), on the device; masked positions read 0."""
    import jax.numpy as jnp

    best = ref_logits.max(axis=-1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(picked)[..., None], axis=-1)[..., 0]
    return jnp.where(jnp.asarray(mask), best - got, 0.0)


def compare(ref, cfg: dict, weights, sample, seq_len: int, rows: int,
            control: bool = False) -> dict:
    """Run the reference once over ``sample``. Returns the numbers: the
    widest and mean gap of the served tokens, the share of them that are
    the reference's first choice, and how many were compared. With
    ``control``, under the key ``control`` the same numbers with the
    control in the served tokens' place: at every position the token that
    the reference puts first in the next precision below the stated one."""
    import jax.numpy as jnp

    tokens, pos, served, mask = pack(sample, seq_len, rows)
    logits = ref.logits_at(cfg, weights, tokens, pos)
    n = int(mask.sum())

    def numbers(picked) -> dict:
        g = gaps(logits, picked, mask)
        return {
            "logit_gap": float(g.max()),
            "logit_gap_mean": float(g.sum()) / n,
            "first_choice_share": float(
                (jnp.where(jnp.asarray(mask), logits.argmax(-1) == picked, False)).sum()) / n,
            "tokens_compared": n,
            "requests_compared": len(sample),
        }

    out = numbers(jnp.asarray(served))
    if control:
        precision = ref.NEXT_LOWER[cfg["torch_dtype"]]
        low = ref.logits_at(cfg, weights, tokens, pos, precision=precision)
        out["control"] = {"precision": precision, **numbers(low.argmax(-1))}
    return out
