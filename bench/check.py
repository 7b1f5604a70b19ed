"""Decides ``correct``: the served tokens against the plain reference.

A sample of the finished requests, drawn from the seed with the longest
of each kind in it, is run through the float32 reference (``model.py``)
once over its prompt and served tokens.  Two numbers are compared, each
against the limit the configuration file states, and each in standard
deviations of the reference's logits at the position (so that a limit
means the same at every width):

  greedy_gap  over greedy tokens: the widest gap by which a served
              token's reference logit lies below the reference's best
              (the model step: prefill, then decode through the cache)
  kept_gap    over sampled tokens: the widest gap by which a served
              token's reference logit lies below the smallest logit the
              reference keeps under the request's top-k, then top-p, at
              its temperature (the sampler's threshold solves)

and, exactly, ``bad_lengths``: finished requests with another number of
tokens than they asked for, or a token outside the vocabulary.

The control (``control.py``, not run by the benchmark) puts the same
reference computed in fp8 in the program's place; ``tests/`` checks that
planted faults come out not correct.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from model import Dims, forward

# Served tokens of each kind (greedy, sampled) the check reads after the
# window: some hundreds, as few as keep the reference shorter than it.
CHECK_TOKENS = 384


def sample(records: list, seed: int) -> list:
    """Finished requests to check: per kind (greedy, sampled), the longest,
    then others in an order drawn from the seed, until ``CHECK_TOKENS``
    served tokens of that kind are in."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    chosen = []
    for greedy in (True, False):
        done = [r for r in records
                if r.tokens is not None and r.req.greedy == greedy]
        if not done:
            continue
        longest = max(done, key=lambda r: (len(r.tokens), -r.req.rid))
        rest = [done[i] for i in rng.permutation(len(done))
                if done[i] is not longest]
        n = 0
        for r in [longest] + rest:
            if n >= CHECK_TOKENS:
                break
            chosen.append(r)
            n += len(r.tokens)
    return chosen


@functools.partial(jax.jit, static_argnames=("dm", "top_k", "quant"))
def _gaps(w, seq, at, served, temperature, top_p, key, *, dm: Dims,
          top_k: int, quant: str | None):
    """Per position ``at`` (the logits that chose ``served``): the
    reference's best logit, its logit of the served token and its
    smallest kept logit.  With ``quant``, also the reference logit of the
    token the quantised model puts first (greedy) and of one it samples
    from its own kept set."""
    ref = forward(w, seq, dm=dm)[at]                        # (N, V)
    best = ref.max(-1)
    tok = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    kept = _kept_floor(ref, temperature, top_p, top_k)
    out = {"best": best, "tok": tok, "kept": kept, "std": ref.std(-1)}
    if quant is not None:
        low = forward(w, seq, dm=dm, quant=quant)[at]
        first = jnp.argmax(low, -1)
        out["low_first"] = jnp.take_along_axis(ref, first[:, None], -1)[:, 0]
        vals, idx = jax.lax.top_k(low / temperature, top_k)
        p = jax.nn.softmax(vals, -1)
        keep = jnp.cumsum(p, -1) - p < top_p
        g = jax.random.gumbel(key, vals.shape)
        pick = jnp.argmax(jnp.where(keep, jnp.log(p) + g, -jnp.inf), -1)
        drawn = jnp.take_along_axis(idx, pick[:, None], -1)
        out["low_drawn"] = jnp.take_along_axis(ref, drawn, -1)[:, 0]
    return out


def _kept_floor(logits, temperature, top_p, top_k):
    """Smallest logit kept by top-k, then top-p over the top-k's softmax,
    at ``temperature``: the nucleus is the shortest prefix of the sorted
    kept tokens whose mass reaches top_p."""
    vals, _ = jax.lax.top_k(logits / temperature, top_k)
    p = jax.nn.softmax(vals, -1)
    keep = jnp.cumsum(p, -1) - p < top_p
    n = jnp.maximum(keep.sum(-1), 1)
    return jnp.take_along_axis(vals, (n - 1)[:, None], -1)[:, 0] * temperature


def readings(w, dm: Dims, records: list, sampler: dict, context: int,
             seed: int, quant: str | None = None) -> dict:
    """The compared numbers over ``records`` (each with its served
    tokens).  With ``quant``, the control's numbers beside them."""
    greedy_gaps, kept_gaps = [], []
    low_greedy, low_kept = [], []
    key = jax.random.key(np.random.SeedSequence([seed, 3]).generate_state(
        1)[0])
    for r in records:
        prompt, toks = r.req.prompt, np.asarray(r.tokens, np.int32)
        n, lp = len(toks), len(prompt)
        seq = np.zeros(context, np.int32)
        seq[:lp] = prompt
        seq[lp:lp + n - 1] = toks[:-1]
        at = np.zeros(context, np.int32)
        at[:n] = lp - 1 + np.arange(n)
        served = np.zeros(context, np.int32)
        served[:n] = toks
        key, sub = jax.random.split(key)
        g = jax.device_get(_gaps(
            w, seq, at, served, np.float32(sampler["temperature"]),
            np.float32(sampler["top_p"]), sub, dm=dm,
            top_k=int(sampler["top_k"]), quant=quant))
        g = {k: v[:n] for k, v in g.items()}
        std = g["std"]
        if r.req.greedy:
            greedy_gaps.append((g["best"] - g["tok"]) / std)
            if quant:
                low_greedy.append((g["best"] - g["low_first"]) / std)
        else:
            kept_gaps.append(np.maximum(g["kept"] - g["tok"], 0.0) / std)
            if quant:
                low_kept.append(
                    np.maximum(g["kept"] - g["low_drawn"], 0.0) / std)

    def widest(parts):
        return float(np.concatenate(parts).max()) if parts else None

    out = {"greedy_gap": widest(greedy_gaps), "kept_gap": widest(kept_gaps),
           "greedy_tokens": int(sum(len(x) for x in greedy_gaps)),
           "sampled_tokens": int(sum(len(x) for x in kept_gaps))}
    if quant:
        out["control_greedy_gap"] = widest(low_greedy)
        out["control_kept_gap"] = widest(low_kept)
    return out


def bad_lengths(records: list, vocab: int) -> int:
    bad = 0
    for r in records:
        if r.tokens is None:
            continue
        t = np.asarray(r.tokens)
        if len(t) != r.req.n_new or (t < 0).any() or (t >= vocab).any():
            bad += 1
    return bad


def judge(found: dict, limits: dict, n_bad: int) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}).  A number the run could not
    read (no finished request of that kind) is left out; a run that reads
    neither is not correct."""
    checks = {"bad_lengths": {"value": n_bad, "limit": 0}}
    ok = n_bad == 0
    for name in ("greedy_gap", "kept_gap"):
        v = found.get(name)
        if v is None:
            continue
        lim = limits.get(name)
        checks[name] = {"value": v, "limit": lim}
        ok = ok and lim is not None and v <= lim
    if found.get("greedy_gap") is None and found.get("kept_gap") is None:
        ok = False
    return ok, checks
