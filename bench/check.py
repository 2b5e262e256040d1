"""The output check that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished is scored by the plain reference of the
configuration (``bench/reference/<family>.py``): one teacher-forced pass
over each prompt with its served tokens. The sample is made of whole
calls drawn from the seed, the first of them holding the longest prompt,
so a call of as many requests as there are slots puts every slot in it.
At every served position the gap is the reference's best logit minus its
logit of the served token (0 where the two agree). Two numbers are
compared, each with its limit in ``limits/<workload>.json`` beside the
readings it was set from: the mean gap over the sample's positions, and
the largest of the sampled requests' own mean gaps, so that one request
or one slot gone wrong cannot hide among sound ones. The widest gap and
the share of positions where the served token is the reference's first
choice are logged beside them, not compared: HiF4 turns last-bit
differences of summation order into logit changes of up to about half a
logit, so the widest gap of sound runs reaches into the control's
(PERF.md, section 2).

The control (``control_tokens``) is the same reference with every value
the configuration stores in bfloat16 stored in float8 e4m3 instead: at
each position, the token that the lower precision puts first. A run with
``control=True`` scores those tokens in place of the served ones and has
to come out not correct.
"""
from __future__ import annotations

import numpy as np


def sample(run) -> list:
    """(prompt, served tokens) of the checked requests: whole calls in an
    order drawn from the seed, the first call that holds the longest
    prompt leading, each call's requests longest first, until
    ``check_requests``."""
    calls = run.calls
    n = int(run.mix["check_requests"])
    rng = np.random.default_rng((run.seed, 7))
    order = [int(i) for i in rng.permutation(len(calls))]
    longest = max(max(c.lengths) for c in calls)
    first = next(i for i in order if max(calls[i].lengths) == longest)
    picks = []
    for i in [first] + [i for i in order if i != first]:
        c = calls[i]
        for j in sorted(range(len(c.rids)), key=lambda j: -c.lengths[j]):
            picks.append((run.traffic.prompt(c.rids[j]), c.tokens[j]))
        if len(picks) >= n:
            break
    return picks[:n]


def batch(pairs, max_prompt: int, new_tokens: int):
    """tokens (n, S), prompt lengths (n,), rows (n, T), served (n, T)."""
    S = max_prompt + new_tokens - 1
    n = len(pairs)
    tokens = np.zeros((n, S), np.int32)
    rows = np.zeros((n, new_tokens), np.int32)
    served = np.zeros((n, new_tokens), np.int32)
    plen = np.zeros((n,), np.int32)
    for i, (prompt, out) in enumerate(pairs):
        P, T = len(prompt), len(out)
        assert T == new_tokens, (T, new_tokens)
        seq = np.concatenate([prompt, out[:-1]])
        tokens[i, : len(seq)] = seq
        rows[i] = P - 1 + np.arange(T)
        served[i] = out
        plen[i] = P
    return tokens, plen, rows, served


def reference_logits(run, pairs, dtype=None):
    """The reference's logits at every served position, one request at a
    time so that the reference holds one sequence besides one layer."""
    import jax
    import jax.numpy as jnp

    kw = {} if dtype is None else {"dtype": dtype}
    out, served = [], []
    for pair in pairs:
        tokens, plen, rows, got = batch(
            [pair], max(run.traffic.lengths), run.traffic.new_tokens)
        logits = run.reference.forward_logits(
            run.sizes, run.key, jnp.asarray(tokens), jnp.asarray(plen),
            jnp.asarray(rows), page=run.traffic.page_tokens, **kw)
        out.append(jax.device_get(logits)[0])
        served.append(got[0])
    return np.stack(out), np.stack(served)


def gaps(logits, tokens) -> np.ndarray:
    """Best logit minus the logit of ``tokens``, at every position."""
    best = logits.max(-1)
    got = np.take_along_axis(logits, tokens[..., None], -1)[..., 0]
    return best - got


def numbers(g) -> dict:
    """The compared numbers of gaps ``g`` (requests, positions)."""
    return {"mean_logit_gap": float(g.mean()),
            "worst_request_gap": float(g.mean(axis=1).max())}


def control_tokens(run, pairs):
    """The tokens the float8-stored reference puts first, teacher-forced
    over the same prompts and served tokens."""
    import jax.numpy as jnp

    low, _ = reference_logits(run, pairs, dtype=jnp.float8_e4m3fn)
    return low.argmax(-1).astype(np.int32)


def check_run(run, limits: dict, log=print, control: bool = False) -> dict:
    """The numbers compared, each with its limit (the result's ``checks``).
    With ``control``, the control's tokens are scored in place of the
    served ones."""
    pairs = sample(run)
    logits, served = reference_logits(run, pairs)
    if control:
        served = control_tokens(run, pairs)
        log("check: CONTROL: the float8-stored reference's tokens in place "
            "of the served ones")
    g = gaps(logits, served)
    log(f"check: {g.size} positions of {len(pairs)} requests; widest gap "
        f"{float(g.max())!r}, served token first {float((g == 0).mean())!r} "
        "(not compared); request mean gaps "
        + " ".join(f"{v:.4f}" for v in g.mean(axis=1)))
    return {name: {"value": value, "limit": float(limits[name])}
            for name, value in numbers(g).items()}
