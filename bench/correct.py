"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed with the one that served the
most tokens in it, goes through the plain reference
(``bench/reference/<family>.py``, float32 at HIGHEST precision) once:
each prompt followed by the tokens the engine served for it. Every served
token came from the engine's greedy argmax, so it should be the
reference's best at its position up to rounding. The number compared is
the widest gap, in logits, by which a served token lies below the
reference's best.

The control puts the reference, computed in bfloat16, in the program's
place: at each position of the same prompts and tokens it reads the gap of
the token that bfloat16 ranks first (``control_gap``). It is run by
``bench/control.py`` and the tests, not by the benchmark's own runs.

Besides the gap, every response must carry a verified MAC, hold exactly
the tokens the engine generated for that request, in range and of the
asked length, and no engine may have crashed: each a count with limit 0.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def pick_sample(done: List[Tuple[object, int]], n: int, seed: int,
                chips: int) -> List[Tuple[object, int]]:
    """``done``: (result, engine index) of finished requests. The request
    with most served tokens, then others drawn from the seed, taking the
    engines in turn so that every replica is checked."""
    if not done:
        return []
    rng = np.random.default_rng([seed % 2**64, 3])
    longest = max(range(len(done)), key=lambda i: len(done[i][0].tokens))
    picked = [longest]
    by_engine = {e: [i for i, (_, ei) in enumerate(done) if ei == e
                     and i != longest] for e in range(chips)}
    for e in by_engine:
        rng.shuffle(by_engine[e])
    e = (done[longest][1] + 1) % chips
    while len(picked) < n and any(by_engine.values()):
        if by_engine[e]:
            picked.append(by_engine[e].pop())
        e = (e + 1) % chips
    return [done[i] for i in picked]


def served_matrix(sample, positions: int):
    """→ tokens (R, T), served (R, T), mask (R, T): position p's logits
    predict the token at p + 1, so served token k of a prompt of length L
    is read at position L - 1 + k."""
    R = len(sample)
    tokens = np.zeros((R, positions), np.int32)
    served = np.zeros((R, positions), np.int32)
    mask = np.zeros((R, positions), bool)
    for r, (res, _) in enumerate(sample):
        prompt, out = res.req.prompt, res.tokens
        seq = np.concatenate([prompt, out]).astype(np.int32)[:positions]
        tokens[r, :len(seq)] = seq
        at = len(prompt) - 1 + np.arange(len(out))
        served[r, at] = out
        mask[r, at] = True
    return tokens, served, mask


def _ref_logits(embed, h, vocab):
    return jnp.einsum("rtd,vd->rtv", h, embed[:vocab].astype(h.dtype),
                      precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("vocab",))
def _gap_of(embed, h_ref, chosen, mask, *, vocab):
    """Widest gap, over masked positions, between the reference's best
    logit and its logit for ``chosen``."""
    ref = _ref_logits(embed, h_ref, vocab)
    got = jnp.take_along_axis(ref, chosen[..., None], -1)[..., 0]
    return jnp.where(mask, ref.max(-1) - got, 0.0).max()


@functools.partial(jax.jit, static_argnames=("vocab",))
def _argmax_of(embed, h, *, vocab):
    logits = jnp.einsum("rtd,vd->rtv", h, embed[:vocab].astype(h.dtype))
    return logits.argmax(-1).astype(jnp.int32)


def gaps(ref, m, params, sample, positions: int, device,
         control: bool = False) -> Dict[str, float]:
    """The served tokens' widest gap under the reference; with
    ``control``, also the gap of bfloat16's first choice."""
    import jax
    import jax.numpy as jnp
    tokens, served, mask = served_matrix(sample, positions)
    with jax.default_device(device):
        tokens, served, mask = map(jnp.asarray, (tokens, served, mask))
        embed = params["embed"]["tok"]
        h = ref.hidden(m, params, tokens, dtype=jnp.float32,
                       precision=jax.lax.Precision.HIGHEST)
        out = {"max_gap": float(_gap_of(embed, h, served, mask,
                                        vocab=m.vocab_size)),
               "served_tokens_checked": int(mask.sum())}
        if control:
            h_ctl = ref.hidden(m, params, tokens, dtype=jnp.bfloat16,
                               precision=jax.lax.Precision.DEFAULT)
            top = _argmax_of(embed, h_ctl, vocab=m.vocab_size)
            out["control_gap"] = float(_gap_of(embed, h, top, mask,
                                               vocab=m.vocab_size))
    return out


def count_checks(results, probe, vocab: int, crashes: int,
                 macs: int) -> Dict[str, int]:
    """Counts with limit 0 over every answered request of the run."""
    answered = [r for r in results if r.tokens is not None]
    bad_len = sum(1 for r in answered
                  if r.tokens.shape != (r.req.max_new,)
                  or r.tokens.min() < 0 or r.tokens.max() >= vocab)
    mismatch = 0
    for r in answered:
        adm = probe.admitted.get(r.key)
        if adm is None or list(adm.request.generated) != r.tokens.tolist():
            mismatch += 1
    return {"unverified_responses": max(0, len(answered) - macs),
            "tokens_not_the_engines": mismatch,
            "wrong_length_or_range": bad_len,
            "engine_crashes": crashes}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """→ (correct, checks) with checks {name: {"value", "limit"}}, every
    number at or under its limit."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(v["value"] is not None and v["value"] <= v["limit"]
             for v in checks.values())
    return ok, checks
