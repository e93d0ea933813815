"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests the run served is
drawn from the seed: the one with the most served tokens, then others at
random, ``sample_requests`` in all.  The plain reference runs once over each
prompt followed by its served tokens, and at every served position reads
how far the served token's logit lies below the reference's best there.
Greedy decoding serves the reference's best token, so in exact arithmetic
every gap is 0; rounding makes near-ties swap.  The number compared is the
mean gap over the sample's served tokens (``mean_logit_gap``): the widest
gap does not separate the program from the control (PERF.md, section 2).

The control puts the reference in the program's place at the next lower
precision (``quant="fp8"``): at each of the same positions it reads the
gap of the token that the float8 computation puts first.
"""

from __future__ import annotations

import importlib

import jax.numpy as jnp
import numpy as np

MIN_PAD = 512           # the reference's attention and MLP block size


def sample(requests, seed: int, n: int) -> list:
    """Up to ``n`` served requests: the one with the most served tokens,
    then a draw from the seed."""
    served = sorted((r for r in requests if r.tokens),
                    key=lambda r: (-len(r.tokens), r.rid))
    if not served:
        return []
    rest = np.random.default_rng(seed).permutation(len(served) - 1)
    return [served[0]] + [served[1 + i] for i in rest[:n - 1]]


def _pow2(n: int, lo: int) -> int:
    return max(lo, 1 << (int(n) - 1).bit_length())


def _inputs(req):
    """(tokens [S], rows [R], served [R], n): the prompt with all but the
    last served token, padded; the rows whose logits predict each served
    token, padded by repeating the last."""
    prompt = np.asarray(req.prompt, np.int32).reshape(-1)
    served = np.asarray(req.tokens, np.int32)
    n = served.size
    seq = np.concatenate([prompt, served[:-1]])
    S = _pow2(seq.size, MIN_PAD)
    tokens = np.zeros((S,), np.int32)
    tokens[:seq.size] = seq
    R = _pow2(n, 64)
    rows = prompt.size - 1 + np.minimum(np.arange(R), n - 1)
    pad = np.concatenate([served, np.full((R - n,), served[-1], np.int32)])
    return tokens, rows.astype(np.int32), pad, n


def reference(mc: dict):
    return importlib.import_module(f"chipbench.references.{mc['reference']}")


def served_gaps(mc: dict, params, reqs,
                control: str | None = None) -> np.ndarray:
    """Per served token, the reference's best logit minus the logit of the
    token served (``control=None``) or of the token the control puts first
    (``control="fp8"``)."""
    ref = reference(mc)
    out = []
    for r in reqs:
        tokens, rows, served, n = _inputs(r)
        t, rw = jnp.asarray(tokens), jnp.asarray(rows)
        logits = ref.logit_rows(mc, params, t, rw)
        if control is not None:
            served = np.asarray(jnp.argmax(
                ref.logit_rows(mc, params, t, rw, quant=control), -1))
        best = np.asarray(jnp.max(logits, -1))
        got = np.asarray(jnp.take_along_axis(
            logits, jnp.asarray(served)[:, None], -1)[:, 0])
        out.append((best - got)[:n])
        del logits
    return np.concatenate(out) if out else np.zeros((0,))


def gap_stats(gaps: np.ndarray) -> dict:
    """Summaries of per-token gaps, for setting a limit."""
    if not gaps.size:
        return {"tokens": 0}
    return {"tokens": int(gaps.size), "max_logit_gap": float(gaps.max()),
            "p99_logit_gap": float(np.percentile(gaps, 99)),
            "mean_logit_gap": float(gaps.mean()),
            "mismatch_share": float((gaps > 0).mean())}
